"""SOT-lite: guarded value-specializing capture (`paddle_tpu/jit/sot.py`).

Ports the reference SOT suite's core patterns (`test/sot/`):
- `test_break_graph.py` ifelse_func / multi_output — value-dependent
  branches with early returns compile as guarded specializations;
- `test_builtin_range.py` test_range_9/10 — `range(int(tensor))` loop
  bounds burn into the program and re-specialize per value;
- `test_builtin_bool.py` — bool() on tensors in boolean expressions;
- `test_instruction_translator_cache_context` pattern — assert
  compile/guard-miss counts, not just outputs;
- break-reason observability (the reference SOT's BreakGraphError log)
  via `paddle.jit.status()`.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit import status, to_static


def t(arr):
    return paddle.to_tensor(np.asarray(arr, np.float32))


# ---------------------------------------------------- branch specialization

@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_ifelse_early_return_specializes():
    """ref test_break_graph.py::ifelse_func — `if` on a tensor value with
    returns inside both arms: two guarded programs, zero eager calls."""
    def f(x, y):
        if x > 0:
            return y + 1      # return inside a traced branch: the AST
        return y - 1          # converter rejects it; SOT takes over

    sf = to_static(f)
    out1 = sf(t(2.0), t(10.0))
    out2 = sf(t(-2.0), t(10.0))
    out3 = sf(t(5.0), t(1.0))        # same branch as call 1: cache hit
    np.testing.assert_allclose(out1.numpy(), 11.0)
    np.testing.assert_allclose(out2.numpy(), 9.0)
    np.testing.assert_allclose(out3.numpy(), 2.0)
    st = sf._stats
    assert st["sot_specializations"] == 2
    assert st["eager_calls"] == 0 and not st["graph_breaks"]


@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_multi_output_branches():
    """ref test_break_graph.py::multi_output — early return of different
    expressions per branch."""
    def f(x):
        m = x + 1
        if x.sum() > 0:
            return m * 2
        return m / 2

    sf = to_static(f)
    np.testing.assert_allclose(sf(t([1.0, 1.0])).numpy(), [4.0, 4.0])
    np.testing.assert_allclose(sf(t([-1.0, -1.0])).numpy(), [0.0, 0.0])
    assert sf._stats["sot_specializations"] == 2


@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_bool_in_expression():
    """ref test_builtin_bool.py — bool(tensor) consumed by Python `and`;
    both truth values specialize."""
    def f(x, flag):
        if bool(x.max() > 1.0) and flag:
            return x * 10
        return x

    sf = to_static(f)
    np.testing.assert_allclose(sf(t([2.0]), True).numpy(), [20.0])
    np.testing.assert_allclose(sf(t([0.5]), True).numpy(), [0.5])
    # flag is a Python arg: different signature, fresh specialization set
    np.testing.assert_allclose(sf(t([2.0]), False).numpy(), [2.0])


# ----------------------------------------------------------- int/item burns

@pytest.mark.slow  # 9s measured: int() burn triggers a per-iteration retrace loop; the other sot fallback burns stay fast
def test_range_over_tensor_bound():
    """ref test_builtin_range.py::test_range_9 — `range(int(tensor))`:
    the bound burns into the unrolled program and guards re-specialize
    when the value changes."""
    def f(x, n):
        acc = x
        for _ in range(int(n)):
            acc = acc + x
        return acc

    sf = to_static(f)
    n3 = paddle.to_tensor(np.int32(3))
    n5 = paddle.to_tensor(np.int32(5))
    np.testing.assert_allclose(sf(t([1.0]), n3).numpy(), [4.0])
    np.testing.assert_allclose(sf(t([1.0]), n5).numpy(), [6.0])
    np.testing.assert_allclose(sf(t([2.0]), n3).numpy(), [8.0])
    assert sf._stats["sot_specializations"] == 2
    assert sf._stats["guard_misses"] >= 1


@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_item_burn_guard():
    """.item() on a traced scalar burns + guards (the scale-factor
    pattern of GradScaler-style host reads)."""
    def f(x, s):
        return x * s.item()

    sf = to_static(f)
    np.testing.assert_allclose(sf(t([3.0]), t(2.0)).numpy(), [6.0])
    np.testing.assert_allclose(sf(t([3.0]), t(4.0)).numpy(), [12.0])
    assert sf._stats["sot_specializations"] == 2


@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_guard_thrash_falls_back():
    """A float burn that never repeats exhausts MAX_SPECIALIZATIONS and
    falls back to eager WITH a recorded reason (no silent thrash)."""
    from paddle_tpu.jit import sot as _sot

    def f(x, s):
        return x * float(s)

    sf = to_static(f)
    with pytest.warns(UserWarning, match="falling back"):
        for i in range(_sot.MAX_SPECIALIZATIONS + 2):
            out = sf(t([1.0]), t(float(i) + 0.5))
    np.testing.assert_allclose(
        out.numpy(), [_sot.MAX_SPECIALIZATIONS + 1.5])
    st = sf._stats
    assert st["graph_breaks"] and "thrash" in st["graph_breaks"][0]["reason"]
    assert st["eager_calls"] >= 1


# -------------------------------------------------------------- observability

@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_status_reports_breaks_and_specs():
    """paddle.jit.status(): the break-reason report the reference SOT
    logs (jit/sot/utils/exceptions.py classes)."""
    def good(x):
        if x.mean() > 0:
            return x + 1
        return x - 1

    def bad(x):
        return x * float(x.numpy().sum())   # host read: unguardable

    sg, sb = to_static(good), to_static(bad)
    sg(t([1.0]))
    sg(t([-1.0]))
    with pytest.warns(UserWarning):
        sb(t([1.0]))
    report = status()
    gs = next(v for k, v in report.items() if k.startswith("good"))
    bs = next(v for k, v in report.items() if k.startswith("bad"))
    assert gs["sot_specializations"] == 2 and not gs["graph_breaks"]
    assert bs["graph_breaks"]
    assert "SOT" in bs["graph_breaks"][0]["reason"]


@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_state_not_committed_on_guard_miss():
    """A guard miss discards the run: parameter mutations from the
    wrong-branch program must NOT land (the no-donation contract)."""
    w = paddle.create_parameter([1], "float32")
    with paddle.no_grad():
        w.set_value(np.array([1.0], np.float32))

    def f(x):
        if x.sum() > 0:
            with paddle.no_grad():
                w.set_value(w * 2.0)
        else:
            with paddle.no_grad():
                w.set_value(w * 3.0)
        return w * x

    sf = to_static(f)
    sf(t([1.0]))                       # spec A: w *= 2 -> w == 2
    np.testing.assert_allclose(w.numpy(), [2.0])
    sf(t([-1.0]))                      # miss on A (discarded), runs B
    np.testing.assert_allclose(w.numpy(), [6.0])
    sf(t([1.0]))                       # miss on B (discarded), back to A
    np.testing.assert_allclose(w.numpy(), [12.0])


@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_closure_constant_concretization_stays_synced():
    """A non-traced (closure-constant) tensor concretized between traced
    burns must consume its burn entry without emitting a guard — the
    later traced burn must not inherit its recorded value."""
    flag = paddle.to_tensor(np.float32(1.0))

    def f(x):
        if flag:                 # closure constant: consumed, unguarded
            x = x + 1
        if x.sum() > 0:          # traced: guarded
            return x * 2
        return x

    sf = to_static(f)
    np.testing.assert_allclose(sf(t([1.0])).numpy(), [4.0])
    np.testing.assert_allclose(sf(t([-3.0])).numpy(), [-2.0])
    np.testing.assert_allclose(sf(t([2.0])).numpy(), [6.0])
    st = sf._stats
    assert st["sot_specializations"] == 2 and not st["graph_breaks"], st


def test_record_trace_divergence_breaks_cleanly():
    """Python state mutated by the function can change which
    concretizations RUN between the record pass and the trace — the
    consumption check must graph-break to eager with a reason, never
    crash or commit an unguarded program."""
    state = {"calls": 0}

    def f(x):
        state["calls"] += 1
        if x.max() < -100:             # always concretized (early return)
            return x
        if state["calls"] % 2 == 0:    # python-only branch, flips per run
            if x.sum() > 0:            # extra burn on even runs only
                return x * 2
        return x - 1

    sf = to_static(f)
    with pytest.warns(UserWarning, match="falling back"):
        out = sf(t([1.5]))     # SOT record (odd) burns 1 value; the
                               # trace (even) hits a second concretization
    assert out is not None
    assert sf._stats["graph_breaks"]
    assert "burn" in sf._stats["graph_breaks"][0]["reason"]

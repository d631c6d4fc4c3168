"""graft-lint (`paddle_tpu/tooling/analyze`) + the jaxsan runtime
sanitizer (`paddle_tpu/testing/jaxsan`), ISSUE 8.

Three layers:
1. per-rule fixture snippets — each rule catches its bad fixture, passes
   its good twin, and honors inline `# graft-lint: disable=RXXX`;
2. the ratchet — baselined findings pass, injected new findings fail,
   `--update-baseline` refreshes, and the REAL tree is clean against the
   committed baseline in under the 30s budget (this test IS the tier-1
   wiring of `python -m paddle_tpu.tooling.analyze --check-baseline`);
3. jaxsan — the in-flight checksum catches a deliberately re-injected
   aliasing race (serving, `unsafe_alias`), donated-leaf poisoning makes
   use-after-donate loud on CPU, and the real-finding fixes from this PR
   each keep a regression test.
"""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.flags import flag_guard
from paddle_tpu.tooling.analyze import (DEFAULT_BASELINE_PATH,
                                        analyze_paths, load_baseline,
                                        new_findings, save_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "paddle_tpu")


def run_src(tmp_path, files, rules=None):
    """Write {name: source} into tmp_path and analyze it."""
    tmp_path.mkdir(parents=True, exist_ok=True)
    for name, src in files.items():
        (tmp_path / name).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / name).write_text(src)
    return analyze_paths([str(tmp_path)], root=str(tmp_path), rules=rules)


def rules_of(findings):
    return sorted({f.rule for f in findings})


# ================================================== per-rule fixtures

R001_BAD = """\
import jax
import numpy as np

def step(x):
    return float(np.asarray(x).sum())

prog = jax.jit(step)
"""

R001_GOOD = """\
import jax
import jax.numpy as jnp
import numpy as np

def step(x):
    return jnp.sum(x)

prog = jax.jit(step)

def host_read(x):          # NOT traced: host syncs are fine here
    return float(np.asarray(x).sum())
"""


def test_r001_catches_host_sync_in_traced_fn(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R001_BAD})
    assert "R001" in rules_of(fs)
    f = next(f for f in fs if f.rule == "R001")
    assert f.path == "mod.py" and f.line == 5 and f.symbol == "step"


def test_r001_passes_good_twin(tmp_path):
    assert run_src(tmp_path, {"mod.py": R001_GOOD}, rules=["R001"]) == []


def test_r001_nested_helper_called_from_traced_is_traced(tmp_path):
    src = """\
import jax
import numpy as np

def helper(v):
    return v.item()

def step(x):
    return helper(x * 2)

prog = jax.jit(step)
"""
    fs = run_src(tmp_path, {"mod.py": src}, rules=["R001"])
    assert len(fs) == 1 and fs[0].symbol == "helper"


R002_BAD = """\
import jax.numpy as jnp

def tick(buf):
    dev = jnp.asarray(buf)
    buf[0] = 1
    return dev
"""

R002_GOOD = """\
import jax.numpy as jnp

def tick(buf):
    dev = jnp.asarray(buf.copy())
    buf[0] = 1
    return dev
"""


def test_r002_catches_mutation_after_handoff(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R002_BAD}, rules=["R002"])
    assert len(fs) == 1 and fs[0].line == 5


def test_r002_private_copy_is_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R002_GOOD}, rules=["R002"]) == []


def test_r002_cross_method_view_race(tmp_path):
    """The PR 3 / `_try_admit` shape: a self-buffer VIEW handed to the
    device in one method, the base mutated by another method."""
    bad = """\
import jax.numpy as jnp

class Engine:
    def dispatch(self):
        return jnp.asarray(self.tables[0:1])

    def evict(self, slot):
        self.tables[slot, :] = 0
"""
    good = bad.replace("self.tables[0:1]", "self.tables[0:1].copy()")
    fs = run_src(tmp_path / "bad", {"mod.py": bad}, rules=["R002"])
    assert len(fs) == 1 and "evict" in fs[0].message
    assert run_src(tmp_path / "good", {"mod.py": good},
                   rules=["R002"]) == []


R003_BAD = """\
import jax

def step(x):
    return x * 2

prog = jax.jit(step, donate_argnums=(0,))

def run(x):
    y = prog(x)
    return x + y
"""

R003_GOOD = """\
import jax

def step(x):
    return x * 2

prog = jax.jit(step, donate_argnums=(0,))

def run(x):
    y = prog(x)
    x = y
    return x + 1
"""


def test_r003_catches_use_after_donate(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R003_BAD}, rules=["R003"])
    assert len(fs) == 1
    assert "argnum 0" in fs[0].message and fs[0].line == 10


def test_r003_rebind_from_outputs_is_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R003_GOOD}, rules=["R003"]) == []


def test_r003_multiline_donated_call_not_self_flagged(tmp_path):
    """A donated call reformatted across lines must not count its own
    argument expression as a post-call use."""
    src = R003_GOOD.replace("    y = prog(x)", "    y = prog(\n        x)")
    assert run_src(tmp_path, {"mod.py": src}, rules=["R003"]) == []


R004_BAD = """\
import jax

def step(x):
    if get_flag("serving_overlap"):
        return x * 2
    return x * FLAGS_scale

prog = jax.jit(step)
"""

R004_GOOD = """\
import jax

def step(x, overlap):
    return x * 2 if overlap else x

def dispatch(x):
    overlap = get_flag("serving_overlap")   # live at dispatch
    return jax.jit(step, static_argnums=(1,))(x, overlap)
"""


def test_r004_catches_trace_time_flag_read(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R004_BAD}, rules=["R004"])
    assert len(fs) == 2                      # get_flag AND FLAGS_* read
    assert {f.line for f in fs} == {4, 6}


def test_r004_dispatch_time_read_is_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R004_GOOD}, rules=["R004"]) == []


R005_BAD = """\
import threading

_lock = threading.Lock()


def enable():
    with _lock:
        set_flags({"x": 1})     # runs on_change hooks under _lock...


def _hook(v):
    with _lock:                 # ...and the hook wants _lock: AB-BA
        pass

define_flag("x", 1, on_change=_hook)
"""

R005_GOOD = """\
import threading

_lock = threading.Lock()


def configure():
    with _lock:
        return get_flag("x")    # reads are a leaf lock: always legal


def enable():
    set_flags({"x": 1})         # mutation OUTSIDE the module lock


def _hook(v):
    with _lock:
        pass

define_flag("x", 1, on_change=_hook)
"""


def test_r005_catches_lock_order_cycle(tmp_path):
    fs = run_src(tmp_path, {"cachemod.py": R005_BAD}, rules=["R005"])
    assert len(fs) >= 2                      # both edges of the cycle
    assert any("flags._hook_lock" in f.message for f in fs)


def test_r005_set_outside_lock_and_reads_under_lock_are_clean(tmp_path):
    assert run_src(tmp_path, {"cachemod.py": R005_GOOD},
                   rules=["R005"]) == []


def test_r005_callback_defined_under_lock_is_not_an_edge(tmp_path):
    """A function DEFINED inside a with-lock block does not run under
    that lock — no false cycle against a legitimate reverse nesting."""
    src = """\
import threading

lock_a = threading.Lock()
lock_b = threading.Lock()


def make_callback():
    with lock_a:
        def cb():
            with lock_b:
                pass
        return cb


def other():
    with lock_b:
        with lock_a:
            pass
"""
    assert run_src(tmp_path, {"mod.py": src}, rules=["R005"]) == []


@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_cli_nonexistent_path_is_an_error(tmp_path):
    """A typoed path must not make the ratchet pass vacuously on zero
    files — missing paths, non-.py files and committed-baseline
    overwrites from a path subset all exit loudly."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tooling.analyze",
         str(tmp_path / "no_such_dir")],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert out.returncode == 2
    assert "no such path" in out.stderr
    notpy = tmp_path / "data.txt"
    notpy.write_text("hello")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tooling.analyze", str(notpy)],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert out.returncode == 2 and "not a Python source" in out.stderr
    # the committed baseline cannot be rewritten from a path subset
    (tmp_path / "ok.py").write_text("x = 1\n")
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tooling.analyze",
         str(tmp_path / "ok.py"), "--update-baseline"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert out.returncode == 2 and "path subset" in out.stderr


def test_set_flags_is_atomic_under_coercion_failure():
    """A bad value anywhere in the dict must leave EVERY flag untouched
    (and run no hooks) — a half-applied dict whose early hooks never ran
    desyncs hook-applied module state from the registry."""
    from paddle_tpu import flags as _flags
    fired = []
    _flags.define_flag("_test_atomic_a", 0, on_change=fired.append)
    _flags.define_flag("_test_atomic_b", 0)
    before = _flags.get_flag("_test_atomic_a")
    with pytest.raises(ValueError):
        _flags.set_flags({"_test_atomic_a": 7, "_test_atomic_b": "nope"})
    assert _flags.get_flag("_test_atomic_a") == before
    assert fired == []
    _flags.set_flags({"_test_atomic_a": 7, "_test_atomic_b": 1})
    assert fired == [7]


R006_BAD = """\
import time
import jax

prog = jax.jit(lambda x: x * 2)


def bench(x):
    t0 = time.perf_counter()
    y = prog(x)
    return time.perf_counter() - t0
"""

R006_GOOD = """\
import time
import jax

prog = jax.jit(lambda x: x * 2)


def bench(x):
    t0 = time.perf_counter()
    y = prog(x)
    jax.block_until_ready(y)
    return time.perf_counter() - t0
"""


def test_r006_catches_unsynced_timing(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R006_BAD}, rules=["R006"])
    assert len(fs) == 1 and fs[0].line == 10


def test_r006_synced_timing_is_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R006_GOOD}, rules=["R006"]) == []


def test_r006_input_side_conversion_is_not_a_sync(tmp_path):
    """np.asarray feeding the dispatch's INPUT runs before enqueue — it
    must not be mistaken for the missing output sync; wrapping the
    dispatch's OUTPUT does count."""
    bad = R006_BAD.replace("    y = prog(x)",
                           "    import numpy as np\n"
                           "    y = prog(np.asarray(x))")
    fs = run_src(tmp_path / "bad", {"mod.py": bad}, rules=["R006"])
    assert len(fs) == 1
    good = R006_BAD.replace("    y = prog(x)",
                            "    import numpy as np\n"
                            "    y = np.asarray(prog(x))")
    assert run_src(tmp_path / "good", {"mod.py": good},
                   rules=["R006"]) == []


R011_BAD = """\
def move_kv(src, dst, root):
    src.export_prefix_cache(root)
    dst._import_prefix_cache(root)
"""

R011_GOOD = """\
from paddle_tpu.testing import jaxsan as _jaxsan


def move_kv(src, dst, root):
    src.export_prefix_cache(root)
    src.release_exported_prefix()
    dst._import_prefix_cache(root)
    _jaxsan.blocksan_verify(dst)


def drain_only(engine, root):      # export alone (drain) is fine
    return engine.export_prefix_cache(root)


def warm_start(engine, root):      # import alone (construction) is fine
    engine._import_prefix_cache(root)
"""


def test_r011_catches_unpaired_handoff(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R011_BAD}, rules=["R011"])
    assert len(fs) == 1 and fs[0].line == 2
    assert "release_exported_prefix" in fs[0].message
    assert "blocksan_verify" in fs[0].message


def test_r011_release_without_verify_still_flags(tmp_path):
    src = R011_BAD.replace(
        "    dst._import_prefix_cache(root)",
        "    src.release_exported_prefix()\n"
        "    dst._import_prefix_cache(root)")
    fs = run_src(tmp_path, {"mod.py": src}, rules=["R011"])
    assert len(fs) == 1
    assert "blocksan_verify" in fs[0].message
    assert "release_exported_prefix" not in fs[0].message.split("without")[1]


def test_r011_paired_handoff_and_lone_legs_are_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R011_GOOD}, rules=["R011"]) == []


R012_BAD = """\
import http.client


def proxy(addr, body):
    headers = {"X-Graft-Trace": "deadbeef"}
    conn = http.client.HTTPConnection(addr)
    conn.request("POST", "/generate", body=body)
    return conn.getresponse()


def disagg(pair, src, dst, root, ids):
    req = Request(ids, max_new_tokens=1)
    src.add_request(req)
    hand_off(src, dst, root)
"""

R012_GOOD = """\
import http.client


def proxy(addr, body, trace_header):
    trace_headers = {"X-Graft-Trace": trace_header}
    conn = http.client.HTTPConnection(addr)
    conn.request("POST", "/generate", body=body, headers=trace_headers)
    return conn.getresponse()


def disagg(pair, src, dst, root, ids, trace_id):
    req = Request(ids, max_new_tokens=1, trace_id=trace_id)
    src.add_request(req)
    hand_off(src, dst, root, trace_id=trace_id)


def no_context(addr, body):        # no trace source in scope: fine
    conn = http.client.HTTPConnection(addr)
    conn.request("POST", "/healthz", body=body)
    return conn.getresponse()
"""


def test_r012_catches_dropped_trace_context(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R012_BAD}, rules=["R012"])
    assert len(fs) == 2
    assert {f.symbol for f in fs} == {"proxy", "disagg"}
    proxy = next(f for f in fs if f.symbol == "proxy")
    assert proxy.line == 7          # the conn.request sink, not the header
    assert "orphan trace" in proxy.message


def test_r012_propagated_and_contextless_scopes_are_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R012_GOOD}, rules=["R012"]) == []


def test_r012_header_kwarg_counts_as_propagation(tmp_path):
    # forwarding via a headers dict whose NAME carries "trace" passes
    src = R012_BAD.replace(
        'conn.request("POST", "/generate", body=body)',
        'conn.request("POST", "/generate", body=body, '
        "headers=trace_headers)")
    fs = run_src(tmp_path, {"mod.py": src}, rules=["R012"])
    assert {f.symbol for f in fs} == {"disagg"}


R013_BAD = """\
from jax.experimental import pallas as pl
import jax


def hot_attention(q, k, v):
    return pl.pallas_call(
        _kernel, out_shape=q, interpret=True)(q, k, v)
"""

R013_GOOD = """\
from jax.experimental import pallas as pl
import jax


def attention(q, k, v, interpret=None):
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return pl.pallas_call(
        _kernel, out_shape=q, interpret=interpret)(q, k, v)


def guarded(q, k, v):
    if jax.default_backend() != "tpu":
        return pl.pallas_call(
            _kernel, out_shape=q, interpret=True)(q, k, v)
    return pl.pallas_call(_kernel, out_shape=q)(q, k, v)


def conditional(q, k, v):
    # a conditional EXPRESSION is not a hardcoded literal either
    return pl.pallas_call(
        _kernel, out_shape=q,
        interpret=True if jax.default_backend() != "tpu" else False,
    )(q, k, v)
"""


def test_r013_catches_hardcoded_interpret_kernel(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R013_BAD}, rules=["R013"])
    assert len(fs) == 1
    assert fs[0].symbol == "hot_attention"
    assert "interpret" in fs[0].message


def test_r013_computed_and_guarded_interpret_are_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R013_GOOD}, rules=["R013"]) == []


def test_r013_inline_disable(tmp_path):
    src = R013_BAD.replace(
        "return pl.pallas_call(",
        "return pl.pallas_call(  # graft-lint: disable=R013")
    assert run_src(tmp_path, {"mod.py": src}, rules=["R013"]) == []


R014_BAD = """\
import jax


def train_step(params, grads, layers):
    for layer in layers:
        full = jax.lax.all_gather(params[layer], "dp", tiled=True)
        grads[layer] = compute(full)
    for layer in layers:
        grads[layer] = jax.lax.psum_scatter(grads[layer], "dp")
    return grads
"""

R014_GOOD = """\
import jax


def make_train_step(layers):
    def device_fn(params, grads):
        # traced: the SAME loop of collectives compiles into one program
        for layer in layers:
            full = jax.lax.all_gather(params[layer], "dp", tiled=True)
            grads[layer] = compute(full)
        return grads
    return jax.jit(device_fn)


def train_step_once(params):
    # not in a loop: a single eager gather per step is a different
    # problem than the per-layer dispatch storm this rule targets
    return jax.lax.all_gather(params, "dp", tiled=True)


def loader(shards):
    # loop + eager collective, but not a step/train scope
    out = []
    for s in shards:
        out.append(jax.lax.all_gather(s, "dp", tiled=True))
    return out
"""


def test_r014_catches_eager_collective_in_step_loop(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R014_BAD}, rules=["R014"])
    assert len(fs) == 2
    assert {f.symbol for f in fs} == {"train_step"}
    assert "all_gather" in fs[0].message
    assert "psum_scatter" in fs[1].message


def test_r014_traced_and_non_step_scopes_are_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R014_GOOD}, rules=["R014"]) == []


def test_r014_inline_disable(tmp_path):
    src = R014_BAD.replace(
        'full = jax.lax.all_gather(',
        'full = jax.lax.all_gather(  # graft-lint: disable=R014').replace(
        'grads[layer] = jax.lax.psum_scatter(',
        'grads[layer] = jax.lax.psum_scatter(  '
        '# graft-lint: disable=R014')
    assert run_src(tmp_path, {"mod.py": src}, rules=["R014"]) == []


R015_BAD = """\
def settle(store, gen):
    store.wait(f"world/{gen}")
    val = store.get(f"world/{gen}")
    store.barrier("rendezvous", 2)
    return val
"""

R015_GOOD = """\
def settle(store, gen, elastic_timeout):
    store.wait(f"world/{gen}", timeout=elastic_timeout)
    val = store.get(f"world/{gen}", timeout=5.0)
    store.barrier("rendezvous", 2, timeout=elastic_timeout)
    opts = {}
    default = opts.get("retries", 3)     # mapping .get, not a store op
    present = store.check(f"world/{gen}")  # check never parks
    return val, default, present
"""


def test_r015_flags_untimed_store_waits(tmp_path):
    """An untimed wait/get/barrier on a store receiver inside launcher
    or elastic-rendezvous code parks forever on a crashed peer — the
    exact hang class the unattended-elastic watchdogs exist to kill."""
    fs = run_src(tmp_path, {"distributed/launch/ctrl.py": R015_BAD},
                 rules=["R015"])
    assert len(fs) == 3
    assert all(f.rule == "R015" for f in fs)
    assert any("wait" in f.message for f in fs)


def test_r015_timed_mapping_get_and_check_are_clean(tmp_path):
    fs = run_src(tmp_path, {"distributed/launch/ctrl.py": R015_GOOD},
                 rules=["R015"])
    assert fs == []


def test_r015_out_of_scope_files_are_silent(tmp_path):
    """The rule is scoped to launcher/rendezvous code: the same calls
    elsewhere (mapping .get idioms abound) stay unflagged."""
    assert run_src(tmp_path, {"inference/util.py": R015_BAD},
                   rules=["R015"]) == []


def test_r015_inline_disable(tmp_path):
    src = R015_BAD.replace(
        'store.wait(f"world/{gen}")',
        'store.wait(f"world/{gen}")  # graft-lint: disable=R015').replace(
        'val = store.get(f"world/{gen}")',
        'val = store.get(f"world/{gen}")  '
        '# graft-lint: disable=R015').replace(
        'store.barrier("rendezvous", 2)',
        'store.barrier("rendezvous", 2)  # graft-lint: disable=R015')
    assert run_src(tmp_path,
                   {"distributed/launch/ctrl.py": src},
                   rules=["R015"]) == []


# ===================================================== suppressions

def test_inline_suppression_same_line(tmp_path):
    src = R002_BAD.replace(
        "    buf[0] = 1", "    buf[0] = 1  # graft-lint: disable=R002")
    assert run_src(tmp_path, {"mod.py": src}, rules=["R002"]) == []


def test_suppression_on_preceding_comment_line(tmp_path):
    src = R002_BAD.replace(
        "    buf[0] = 1",
        "    # graft-lint: disable=R002\n    buf[0] = 1")
    assert run_src(tmp_path, {"mod.py": src}, rules=["R002"]) == []


def test_suppression_disable_all_and_wrong_rule(tmp_path):
    allsrc = R002_BAD.replace(
        "    buf[0] = 1", "    buf[0] = 1  # graft-lint: disable=all")
    assert run_src(tmp_path, {"mod.py": allsrc}, rules=["R002"]) == []
    wrong = R002_BAD.replace(
        "    buf[0] = 1", "    buf[0] = 1  # graft-lint: disable=R001")
    assert len(run_src(tmp_path, {"mod.py": wrong}, rules=["R002"])) == 1


def test_finding_format_is_stable(tmp_path):
    import re
    fs = run_src(tmp_path, {"mod.py": R002_BAD}, rules=["R002"])
    assert re.match(r"^mod\.py:\d+:\d+: R002 \[.*\] ", fs[0].format())


# ========================================================= ratchet

def test_ratchet_baseline_pass_inject_fail_update(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R002_BAD})
    baseline_path = tmp_path / "baseline.json"
    save_baseline(str(baseline_path), fs)
    # baselined finding: clean
    assert new_findings(fs, load_baseline(str(baseline_path))) == []
    # inject a NEW violation in another file: exactly it is reported
    (tmp_path / "mod2.py").write_text(R003_BAD)
    fs2 = analyze_paths([str(tmp_path)], root=str(tmp_path))
    fresh = new_findings(fs2, load_baseline(str(baseline_path)))
    assert rules_of(fresh) == ["R003"]
    # update-baseline refreshes: clean again
    save_baseline(str(baseline_path), fs2)
    assert new_findings(fs2, load_baseline(str(baseline_path))) == []


def test_ratchet_fingerprints_survive_line_drift(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R002_BAD})
    baseline_path = tmp_path / "baseline.json"
    save_baseline(str(baseline_path), fs)
    # prepend comments: every line number shifts, fingerprints must not
    (tmp_path / "mod.py").write_text("# moved\n# around\n" + R002_BAD)
    fs2 = analyze_paths([str(tmp_path)], root=str(tmp_path))
    assert fs2[0].line != fs[0].line
    assert new_findings(fs2, load_baseline(str(baseline_path))) == []


@pytest.mark.slow   # tier-1 budget (ISSUE 9): heavy, not on the serving/training core path
def test_cli_clean_tree_exits_zero_and_violation_exits_nonzero(tmp_path):
    """The acceptance contract: the committed baseline makes a clean run
    exit 0; one injected violation exits non-zero."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    clean = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tooling.analyze"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert clean.returncode == 0, clean.stdout + clean.stderr
    assert "0 new" in clean.stdout
    (tmp_path / "violation.py").write_text(R001_BAD)
    bad = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tooling.analyze",
         str(tmp_path)],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "R001" in bad.stdout
    # --update-baseline to a scratch file turns the same run green
    scratch = tmp_path / "b.json"
    upd = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tooling.analyze",
         str(tmp_path), "--baseline", str(scratch), "--update-baseline"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert upd.returncode == 0
    ok = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tooling.analyze",
         str(tmp_path), "--baseline", str(scratch)],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert ok.returncode == 0


def test_tier1_ratchet_tree_is_clean_within_budget():
    """THE tier-1 gate: graft-lint (all ten rules) over the full
    default tree — package, drivers AND tests/ (R010's surface) — vs
    the committed baseline.  Any new finding fails CI here, and the run
    must fit the 30s acceptance budget."""
    from paddle_tpu.tooling.analyze.__main__ import default_paths
    paths = default_paths()
    assert any(p.endswith("tests") for p in paths)   # R010's surface
    t0 = time.perf_counter()
    findings = analyze_paths(paths, root=REPO)
    elapsed = time.perf_counter() - t0
    fresh = new_findings(findings, load_baseline(DEFAULT_BASELINE_PATH))
    assert fresh == [], "new graft-lint findings (fix or baseline " \
        "them):\n" + "\n".join(f.format() for f in fresh)
    assert elapsed < 30.0, f"graft-lint took {elapsed:.1f}s (budget 30s)"


# ================================================ jaxsan (runtime half)

@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
    paddle.seed(0)
    m = GPTForCausalLM(gpt3_tiny())
    m.eval()
    return m


def test_jaxsan_checksum_catches_inflight_mutation_api():
    from paddle_tpu.testing import jaxsan
    with flag_guard(enable_jaxsan=True):
        tok = jaxsan.token("unit.site")
        buf = np.arange(8, dtype=np.int32)
        fed = jaxsan.shield(tok, buf)
        fed[3] = 99                       # mutate what the device sees
        with pytest.raises(jaxsan.JaxsanError, match="unit.site"):
            jaxsan.verify(tok)


def test_jaxsan_disabled_is_noop_copy():
    from paddle_tpu.testing import jaxsan
    with flag_guard(enable_jaxsan=False):
        assert jaxsan.token("x") is None
        buf = np.arange(4)
        out = jaxsan.shield(None, buf)
        assert out is not buf and np.array_equal(out, buf)
        jaxsan.verify(None)               # None-safe


def test_jaxsan_serving_catches_reinjected_alias_race(model):
    """Arm `unsafe_alias` (drop the private copies the PR 3 fix added)
    and the scheduler's own post-dispatch bookkeeping must trip the
    harvest checksum — the race class fails LOUD instead of corrupting
    decode state."""
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.testing import jaxsan
    p = np.asarray([5, 6, 7], np.int32)
    with flag_guard(enable_jaxsan=True):
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16)
        eng.add_request(Request(p, max_new_tokens=6))
        with jaxsan.unsafe_alias():
            with pytest.raises(jaxsan.JaxsanError, match="serving.tick"):
                eng.run()


def test_jaxsan_serving_clean_run_token_parity(model):
    """With the sanitizer ON but no fault armed, serving behaves
    bit-identically (the shield is the same private copy) and the
    checksums all verify."""
    from paddle_tpu.inference.serving import Request, ServingEngine
    from paddle_tpu.observability import metrics as _metrics
    p = np.asarray([5, 6, 7], np.int32)

    def serve():
        eng = ServingEngine(model, max_batch=2, max_context=64,
                            block_size=16)
        r = eng.add_request(Request(p, max_new_tokens=6))
        eng.run()
        return list(r.output_ids)

    with flag_guard(enable_jaxsan=False):
        plain = serve()
    _metrics.reset()
    with flag_guard(enable_jaxsan=True):
        sanitized = serve()
    assert sanitized == plain
    snap = _metrics.snapshot()
    checks = snap["jaxsan.checks"]["series"][0]["value"]
    assert checks > 0
    assert "jaxsan.violations" not in snap or not \
        snap["jaxsan.violations"]["series"]


def test_jaxsan_poison_makes_use_after_donate_loud():
    """CPU ignores donation, so reading a donated buffer 'works' in CPU
    tests; poisoned, it raises immediately."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.testing import jaxsan
    with flag_guard(enable_jaxsan=True):
        prog = jax.jit(lambda a: a + 1, donate_argnums=(0,))
        x = jnp.arange(4.0)
        y = prog(x)
        n = jaxsan.poison_donated([x], site="unit.donate", keep=[y])
        assert n == 1
        with pytest.raises(RuntimeError):
            np.asarray(x)                 # deleted buffer: loud
        np.testing.assert_allclose(np.asarray(y), [1, 2, 3, 4])


def test_jaxsan_fused_optimizer_poisons_stale_param_refs():
    """The fused-optimizer contract (PR 4): params/masters/states are
    donated to the one-step program.  Under jaxsan, a stale reference to
    a pre-step buffer raises instead of silently reading pre-update
    bytes; the optimizer itself keeps stepping normally."""
    from paddle_tpu import nn, optimizer
    paddle.seed(0)
    net = nn.Linear(4, 4)
    opt = optimizer.Adam(learning_rate=0.1,
                         parameters=net.parameters())
    x = paddle.to_tensor(np.random.RandomState(0)
                         .rand(2, 4).astype(np.float32))

    def one_step():
        loss = net(x).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()

    with flag_guard(enable_jaxsan=True, fused_optimizer=True):
        one_step()                        # builds + runs fused program
        stale = net.parameters()[0]._value
        one_step()                        # donates/poisons `stale`
        with pytest.raises(RuntimeError):
            np.asarray(stale)
        one_step()                        # still stepping fine
    live = np.asarray(net.parameters()[0]._value)
    assert np.all(np.isfinite(live))


# ==================================== real-finding fix regressions

def test_fixed_serving_and_executor_are_lint_clean():
    """The two analyzer-surfaced fixes stay fixed: serving's prefill
    table-row handoff (R002) and the executor fetch path (R001)."""
    fs = analyze_paths(
        [os.path.join(PKG, "inference", "serving.py"),
         os.path.join(PKG, "static", "executor.py")], root=REPO)
    assert [f for f in fs if f.rule in ("R001", "R002")] == []


def test_plan_save_snapshot_owns_its_bytes():
    """plan_save's documented contract — 'caller may donate after it
    returns' — requires REAL copies: np.asarray of a CPU jax array is a
    zero-copy view of the live buffer (the R002/R003 class this PR
    fixed in distributed/checkpoint)."""
    import jax.numpy as jnp
    from paddle_tpu.distributed.checkpoint.save_state_dict import \
        plan_save
    src = jnp.arange(16.0).reshape(4, 4)
    t = paddle.to_tensor(np.zeros((4, 4), np.float32))
    t._value = src
    rng_state = np.arange(8, dtype=np.int64)        # numpy leaf
    plan = plan_save({"w": t, "rng": rng_state})
    for arr in plan.payload.values():
        assert not np.shares_memory(arr, np.asarray(src))
        assert not np.shares_memory(arr, rng_state)
    # the donation itself: delete the source buffer, snapshot survives
    src.delete()
    rng_state.fill(-1)
    w = next(v for k, v in plan.payload.items() if k.startswith("w|"))
    np.testing.assert_allclose(w, np.arange(16.0).reshape(4, 4))
    r = next(v for k, v in plan.payload.items() if k.startswith("rng|"))
    np.testing.assert_array_equal(r, np.arange(8))


def test_dataloader_private_copies_for_reused_custom_collate_buffer():
    """io/ prefetch fix (R002 class): a custom collate_fn that refills
    ONE buffer per batch must not alias the in-flight device input —
    every consumed batch keeps its own values even when the producer
    thread runs ahead."""
    from paddle_tpu import io

    class Counting(io.Dataset):
        def __len__(self):
            return 6

        def __getitem__(self, i):
            return i

    shared = np.zeros((2,), np.float32)

    def reusing_collate(samples):
        shared[:] = samples               # the footgun: one live buffer
        return shared

    loader = io.DataLoader(Counting(), batch_size=2,
                           collate_fn=reusing_collate)
    assert loader._batches_need_copy()
    with flag_guard(dataloader_device_prefetch=True):
        seen = []
        for batch in loader:
            time.sleep(0.05)              # let the producer run ahead
            seen.append(np.asarray(batch).tolist())
    assert seen == [[0.0, 1.0], [2.0, 3.0], [4.0, 5.0]]
    # default collate allocates fresh arrays: no copy tax
    assert not io.DataLoader(Counting(),
                             batch_size=2)._batches_need_copy()


def test_set_flags_hooks_run_outside_registry_lock():
    """R005 root-cause fix: an on_change hook that takes a module lock,
    while another thread holds that module lock and reads a flag, must
    NOT AB-BA deadlock (it did when hooks ran under the flags lock)."""
    from paddle_tpu import flags as _flags
    mod_lock = threading.Lock()
    in_reader = threading.Event()
    release_reader = threading.Event()

    def hook(_v):
        with mod_lock:
            pass

    _flags.define_flag("_test_r005_hook_flag", 0, on_change=hook)

    read_val = []

    def reader():
        with mod_lock:
            in_reader.set()
            release_reader.wait(5)
            read_val.append(_flags.get_flag("_test_r005_hook_flag"))

    done = []

    def setter():
        _flags.set_flags({"_test_r005_hook_flag": 1})
        done.append(True)

    rt = threading.Thread(target=reader, daemon=True)
    st = threading.Thread(target=setter, daemon=True)
    rt.start()
    assert in_reader.wait(5)
    st.start()
    time.sleep(0.2)                       # let the setter reach the hook
    release_reader.set()
    rt.join(5)
    st.join(5)
    assert not rt.is_alive() and not st.is_alive(), \
        "AB-BA deadlock between the flags lock and a module lock"
    assert done == [True] and read_val == [1]


def test_executor_fetch_numpy_conversion_stays_eager():
    """Executor fix (R001): fetch returns numpy on the eager path and
    the compiled path, with no numpy materialization inside capture."""
    from paddle_tpu import static as pstatic
    from paddle_tpu.static.executor import CompiledProgram, Executor
    main = pstatic.Program()
    start = pstatic.Program()
    with pstatic.program_guard(main, start):
        a = pstatic.data("a", (2, 2), "float32")
        out = (a * 2.0) + 1.0
    exe = Executor()
    feed = {"a": np.ones((2, 2), np.float32)}
    eager = exe.run(main, feed=feed, fetch_list=[out])
    np.testing.assert_allclose(eager[0], np.full((2, 2), 3.0))
    compiled = exe.run(CompiledProgram(main), feed=feed, fetch_list=[out],
                       return_numpy=True)
    assert isinstance(compiled[0], np.ndarray)
    np.testing.assert_allclose(compiled[0], np.full((2, 2), 3.0))


# ====================== R007-R010: the interprocedural rules (ISSUE 12)

R007_BAD_RETURN = """\
class Engine:
    def _alloc_block(self):
        return self.free.popleft()

    def _release_block(self, b):
        self.free.append(b)

    def admit(self, req):
        blk = self._alloc_block()
        if not req.ok:
            return False
        self.table[0] = blk
        return True
"""

R007_GOOD_RETURN = R007_BAD_RETURN.replace(
    "        if not req.ok:\n            return False",
    "        if not req.ok:\n"
    "            self._release_block(blk)\n            return False")

R007_GOOD_HELPER = R007_BAD_RETURN.replace(
    "        if not req.ok:\n            return False",
    "        if not req.ok:\n"
    "            self._undo(blk)\n            return False") + """\

    def _undo(self, b):
        self._release_block(b)
"""

R007_BAD_DISPATCH = """\
import jax.numpy as jnp

class Engine:
    def _alloc_block(self):
        return self.free.popleft()

    def _release_block(self, b):
        self.free.append(b)

    def admit(self, prompt):
        blk = self._alloc_block()
        row = self.prefill(jnp.asarray(prompt))
        self.table[0] = blk
        return row
"""

R007_GOOD_DISPATCH = R007_BAD_DISPATCH.replace(
    "        row = self.prefill(jnp.asarray(prompt))",
    "        try:\n"
    "            row = self.prefill(jnp.asarray(prompt))\n"
    "        except BaseException:\n"
    "            self._release_block(blk)\n"
    "            raise")


def test_r007_catches_early_return_leak(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R007_BAD_RETURN}, rules=["R007"])
    assert len(fs) == 1 and fs[0].symbol == "Engine.admit"
    assert "returns early" in fs[0].message


def test_r007_release_on_path_is_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R007_GOOD_RETURN},
                   rules=["R007"]) == []


def test_r007_release_via_local_helper_is_clean(tmp_path):
    """The interprocedural half: `_undo(blk)` releases through its
    transitive call summary, so the early return is balanced."""
    assert run_src(tmp_path, {"mod.py": R007_GOOD_HELPER},
                   rules=["R007"]) == []


def test_r007_unguarded_dispatch_exception_edge(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R007_BAD_DISPATCH},
                 rules=["R007"])
    assert len(fs) == 1 and "can raise" in fs[0].message


def test_r007_guarded_dispatch_is_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R007_GOOD_DISPATCH},
                   rules=["R007"]) == []


def test_r007_escape_to_owner_state_before_dispatch_is_clean(tmp_path):
    """The serving `_dispatch_tick` shape: the drawn block lands in the
    table row BEFORE the dispatch — ownership escaped, nothing held."""
    src = R007_BAD_DISPATCH.replace(
        "        row = self.prefill(jnp.asarray(prompt))\n"
        "        self.table[0] = blk\n",
        "        self.table[0] = blk\n"
        "        row = self.prefill(jnp.asarray(prompt))\n")
    assert run_src(tmp_path, {"mod.py": src}, rules=["R007"]) == []


def test_r007_anonymous_acquisition_is_a_leak(tmp_path):
    src = R007_BAD_RETURN.replace(
        "        blk = self._alloc_block()",
        "        self._alloc_block()")
    fs = run_src(tmp_path, {"mod.py": src}, rules=["R007"])
    assert fs and all(f.rule == "R007" for f in fs)


R008_BAD = """\
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map


def body(x, w):
    return jnp.matmul(x, w)


def build(mesh):
    return shard_map(body, mesh=mesh, in_specs=(P(), P("tp", None)),
                     out_specs=P())
"""

R008_GOOD_PSUM = R008_BAD.replace(
    "def body(x, w):\n    return jnp.matmul(x, w)",
    "def body(x, w):\n    y = jnp.matmul(x, w)\n"
    "    return jax.lax.psum(y, \"tp\")")

R008_GOOD_COLUMN = R008_BAD.replace(
    'in_specs=(P(), P("tp", None))',
    'in_specs=(P(), P(None, "tp"))')


def test_r008_catches_partial_escape(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R008_BAD}, rules=["R008"])
    assert len(fs) == 1 and fs[0].symbol == "body"
    assert "psum" in fs[0].message


def test_r008_psum_before_return_is_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R008_GOOD_PSUM},
                   rules=["R008"]) == []


def test_r008_column_parallel_is_clean(tmp_path):
    """Sharded on the OUTPUT (non-contracted) dim: each rank computes
    exact column slices — the TP bit-parity layout; must not flag."""
    assert run_src(tmp_path, {"mod.py": R008_GOOD_COLUMN},
                   rules=["R008"]) == []


def test_r008_einsum_contracted_sharded_letter(tmp_path):
    src = R008_BAD.replace(
        "    return jnp.matmul(x, w)",
        "    return jnp.einsum(\"ij,jk->ik\", x, w)").replace(
        'in_specs=(P(), P("tp", None))',
        'in_specs=(P(), P("tp", None))')
    fs = run_src(tmp_path, {"mod.py": src}, rules=["R008"])
    assert len(fs) == 1
    good = src.replace("jk->ik\", x, w)", "jk->ijk\", x, w)")
    assert run_src(tmp_path / "g", {"mod.py": good},
                   rules=["R008"]) == []


def test_r008_spec_tuple_concat_and_unknown_specs_skipped(tmp_path):
    """The serving idiom `(unknown, helper()) + (P(),) * N` parses; a
    param with an unresolvable spec is skipped, not guessed."""
    src = """\
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map


def body(params, pools, x, w):
    return jnp.matmul(x, w)


def build(mesh, param_specs, pool_spec):
    return shard_map(body, mesh=mesh,
                     in_specs=(param_specs, pool_spec())
                     + (P(),) * 1 + (P("tp", None),),
                     out_specs=P())
"""
    fs = run_src(tmp_path, {"mod.py": src}, rules=["R008"])
    assert len(fs) == 1          # w (sharded on its contracted dim 0)


R009_BAD = """\
import jax


class Server:
    def __init__(self):
        self._fns = {}
        self.scale = 1.0

    def program(self, k):
        fn = self._fns.get(k)
        if fn is not None:
            return fn

        def step(x):
            if get_flag("fast_mode"):
                return x * k
            return x + self.scale

        fn = self._fns[k] = jax.jit(step)
        return fn

    def retune(self, s):
        self.scale = s
"""

R009_GOOD_INVALIDATE = R009_BAD.replace(
    "    def retune(self, s):\n        self.scale = s",
    "    def retune(self, s):\n        self.scale = s\n"
    "        self._fns = {}").replace(
    "            if get_flag(\"fast_mode\"):\n                return x * k\n", "")

R009_GOOD_FROZEN = """\
import jax


class Server:
    def __init__(self):
        self._fns = {}
        self.scale = 1.0

    def program(self, k):
        fn = self._fns.get(k)
        if fn is not None:
            return fn

        def step(x):
            return x + self.scale       # init-frozen: covered

        fn = self._fns[k] = jax.jit(step)
        return fn
"""


def test_r009_catches_flag_and_mutable_attr_reads(tmp_path):
    fs = run_src(tmp_path, {"mod.py": R009_BAD}, rules=["R009"])
    msgs = " | ".join(f.message for f in fs)
    assert len(fs) == 2
    assert "get_flag" in msgs and "self.scale" in msgs
    assert all(f.symbol == "Server.program" for f in fs)


def test_r009_cache_invalidating_mutator_is_clean(tmp_path):
    """`retune` resets the cache alongside the mutation — no stale
    program can survive; must not flag."""
    assert run_src(tmp_path, {"mod.py": R009_GOOD_INVALIDATE},
                   rules=["R009"]) == []


def test_r009_init_frozen_attr_is_clean(tmp_path):
    assert run_src(tmp_path, {"mod.py": R009_GOOD_FROZEN},
                   rules=["R009"]) == []


def test_r009_factory_store_is_followed(tmp_path):
    """The serving TP twin: `fn = self._fns[k] = self._build(k)` routes
    the traced body through a factory METHOD — its reads bake too."""
    src = """\
import jax


class Server:
    def __init__(self):
        self._fns = {}
        self.mode = "a"

    def program(self, k):
        fn = self._fns.get(k)
        if fn is not None:
            return fn
        if k > 4:
            fn = self._fns[k] = self._build(k)
            return fn

        def step(x):
            return x * k

        fn = self._fns[k] = jax.jit(step)
        return fn

    def _build(self, k):
        def step(x):
            return x * k if self.mode == "a" else x
        return jax.jit(step)

    def set_mode(self, m):
        self.mode = m
"""
    fs = run_src(tmp_path, {"mod.py": src}, rules=["R009"])
    assert len(fs) == 1 and "self.mode" in fs[0].message


def test_r009_dispatch_time_reads_in_builder_scope_are_clean(tmp_path):
    """Reads in the builder's own scope feed the program as INPUTS at
    dispatch (the grad-scaler shape) — only traced-body reads bake."""
    src = """\
import jax


class Server:
    def __init__(self):
        self._fns = {}
        self.scale = 1.0

    def program(self, k, x):
        fn = self._fns.get(k)
        if fn is None:
            def step(v, s):
                return v * s
            fn = self._fns[k] = jax.jit(step)
        return fn(x, self.scale)        # live input, not baked

    def retune(self, s):
        self.scale = s
"""
    assert run_src(tmp_path, {"mod.py": src}, rules=["R009"]) == []


def test_r009_per_k_spec_cache_pin(tmp_path):
    """ISSUE 13 lint satellite: the serving engine's per-k speculative
    program caches (`_spec_fns[k]` / `_spec_hd_fns[k]`, kind chosen by
    an init-frozen attribute, builders reading only init-frozen state
    and their own k argument) are exactly the audited-correct shape —
    R009 must stay quiet.  The bad twin keys the same cache on a BARE
    spec flag while the traced body reads the controller-mutated
    `k_now` — under-keyed (k baked at first trace, silently stale
    after every adaptive step), and R009 must say so."""
    good = """\
import jax


class Engine:
    def __init__(self):
        self._spec_fns = {}
        self._spec_hd_fns = {}
        self.spec_kind = "ngram"        # init-frozen
        self.spec_ladder = (2, 4, 8)    # init-frozen

    def spec_program(self, k):
        fn = self._spec_fns.get(k)
        if fn is not None:
            return fn

        def tick(x):
            return x * k                # keyed: k IS the cache key

        fn = self._spec_fns[k] = jax.jit(tick)
        return fn

    def spec_hd_program(self, k):
        fn = self._spec_hd_fns.get(k)
        if fn is not None:
            return fn

        def tick(x):
            return x + len(self.spec_ladder)   # init-frozen: covered

        fn = self._spec_hd_fns[k] = jax.jit(tick)
        return fn
"""
    assert run_src(tmp_path, {"mod.py": good}, rules=["R009"]) == []
    bad = """\
import jax


class Engine:
    def __init__(self):
        self._spec_fns = {}
        self.k_now = 2

    def spec_program(self, spec_on):
        fn = self._spec_fns.get(spec_on)
        if fn is not None:
            return fn

        def tick(x):
            return x * self.k_now       # mutable: baked at first trace

        fn = self._spec_fns[spec_on] = jax.jit(tick)
        return fn

    def adapt(self):
        self.k_now = 4
"""
    fs = run_src(tmp_path, {"mod.py": bad}, rules=["R009"])
    assert len(fs) == 1 and "self.k_now" in fs[0].message


R010_BAD_SUBPROCESS = """\
import subprocess
import sys


def test_spawns_child(tmp_path):
    out = subprocess.run([sys.executable, "-c", "print(1)"])
    assert out.returncode == 0
"""

R010_BAD_LOOP = """\
def test_long_training_loop(model, opt):
    for _ in range(50):
        loss = model()
        loss.backward()
        opt.step()
"""


def test_r010_catches_subprocess_and_loop(tmp_path):
    fs = run_src(tmp_path, {"test_mod.py": R010_BAD_SUBPROCESS,
                            "test_loop.py": R010_BAD_LOOP},
                 rules=["R010"])
    assert len(fs) == 2
    msgs = " | ".join(f.message for f in fs)
    assert "subprocess" in msgs and "range(50)" in msgs


def test_r010_slow_mark_and_module_pytestmark_exempt(tmp_path):
    marked = "import pytest\n\n\n@pytest.mark.slow\n" + \
        R010_BAD_SUBPROCESS.replace("import subprocess\nimport sys\n\n\n",
                                    "import subprocess\nimport sys\n\n")
    module = "import pytest\n\npytestmark = pytest.mark.slow\n\n" + \
        R010_BAD_LOOP
    assert run_src(tmp_path, {"test_marked.py": marked,
                              "test_module.py": module},
                   rules=["R010"]) == []


def test_r010_only_sees_test_files_and_code_rules_skip_them(tmp_path):
    """The scoping contract: R010 ignores non-test modules; R001-R009
    ignore `test_*` modules (they deliberately WRITE the bad patterns
    as fixtures)."""
    fs = run_src(tmp_path, {"mod.py": R010_BAD_SUBPROCESS.replace(
        "def test_spawns_child", "def test_x")}, rules=["R010"])
    assert fs == []
    fs = run_src(tmp_path / "b", {"test_mod.py": R002_BAD})
    assert [f for f in fs if f.rule == "R002"] == []


def test_new_rule_fingerprints_survive_line_drift(tmp_path):
    """Ratchet stability for the v2 rules: prepending comments shifts
    every line; fingerprints must not move."""
    for name, src, rule in [("r7.py", R007_BAD_RETURN, "R007"),
                            ("r8.py", R008_BAD, "R008"),
                            ("r9.py", R009_BAD, "R009"),
                            ("test_r10.py", R010_BAD_SUBPROCESS,
                             "R010")]:
        d = tmp_path / rule
        fs = run_src(d, {name: src}, rules=[rule])
        assert fs, rule
        baseline_path = d / "baseline.json"
        save_baseline(str(baseline_path), fs)
        (d / name).write_text("# drift\n# drift\n" + src)
        fs2 = analyze_paths([str(d / name)], root=str(d), rules=[rule])
        assert fs2[0].line != fs[0].line
        assert new_findings(fs2, load_baseline(str(baseline_path))) \
            == [], rule


def test_r007_suppression(tmp_path):
    src = R007_BAD_RETURN.replace(
        "            return False",
        "            return False  # graft-lint: disable=R007")
    assert run_src(tmp_path, {"mod.py": src}, rules=["R007"]) == []


# ====================== blocksan: the serving refcount ledger (ISSUE 12)

def _drained_engine(model, **kw):
    from paddle_tpu.inference.serving import Request, ServingEngine
    eng = ServingEngine(model, max_batch=2, max_context=64,
                        block_size=16, **kw)
    req = eng.add_request(Request(np.arange(1, 20, dtype=np.int32),
                                  max_new_tokens=6))
    eng.run()
    return eng, list(req.output_ids)


def test_blocksan_clean_run_is_violation_free_and_bit_identical(model):
    """The acceptance pin: a clean serving run under
    FLAGS_enable_jaxsan verifies at every boundary, registers prefix
    checksums, trips nothing, and emits the SAME tokens."""
    from paddle_tpu.observability import metrics as _metrics
    with flag_guard(enable_jaxsan=False):
        _, plain = _drained_engine(model, prefix_cache=True)
    _metrics.reset()
    with flag_guard(enable_jaxsan=True):
        eng, sanitized = _drained_engine(model, prefix_cache=True)
    assert sanitized == plain
    assert eng._blocksan is not None
    assert eng._blocksan.verifies > 0
    assert len(eng._blocksan.digests) > 0      # registered + checksummed
    snap = _metrics.snapshot()
    sites = {s["labels"].get("site"): s["value"]
             for s in snap["jaxsan.checks"]["series"]}
    assert sites.get("serving.blocksan", 0) > 0
    assert "jaxsan.violations" not in snap or not \
        snap["jaxsan.violations"]["series"]


def test_blocksan_disabled_is_none_ledger(model):
    with flag_guard(enable_jaxsan=False):
        eng, _ = _drained_engine(model)
    assert eng._blocksan is None


def test_blocksan_catches_injected_block_leak(model):
    """Chaos injection: draw a block through the accounting path and
    store it nowhere — the boundary reconciliation must name it."""
    from paddle_tpu.testing import jaxsan
    with flag_guard(enable_jaxsan=True):
        eng, _ = _drained_engine(model)
        eng._alloc_block()                     # leaked on purpose
        with pytest.raises(jaxsan.JaxsanError, match="block_leak"):
            jaxsan.blocksan_verify(eng)


def test_blocksan_catches_double_release(model):
    from paddle_tpu.testing import jaxsan
    with flag_guard(enable_jaxsan=True):
        eng, _ = _drained_engine(model)
        blk = eng._alloc_block()
        eng._release_block(blk)
        with pytest.raises(jaxsan.JaxsanError, match="double_release"):
            eng._release_block(blk)


def test_blocksan_catches_accounting_bypass(model):
    """A refcount mutated WITHOUT the accessors (the class the static
    R007 rule cannot see at run time) trips the ledger comparison."""
    from paddle_tpu.testing import jaxsan
    with flag_guard(enable_jaxsan=True):
        eng, _ = _drained_engine(model)
        blk = eng._alloc_block()
        eng.block_rc[blk] += 1                 # bypassing _ref_block
        with pytest.raises(jaxsan.JaxsanError,
                           match="accounting_mismatch"):
            jaxsan.blocksan_verify(eng)


def test_blocksan_catches_registered_block_mutation(model):
    """Immutability checksums: mutating a prefix-registered block's
    pool bytes (what a buggy decode/spec-draft/CoW write would do)
    fails the boundary verify."""
    from paddle_tpu.testing import jaxsan
    with flag_guard(enable_jaxsan=True):
        eng, _ = _drained_engine(model, prefix_cache=True)
        assert eng._blocksan.digests
        blk = next(iter(eng._blocksan.digests))
        kk, vv = eng.pools[0]
        eng.pools[0] = (kk.at[:, blk, 0, 0].add(1.0), vv)
        with pytest.raises(jaxsan.JaxsanError,
                           match="registered_block_mutation"):
            jaxsan.blocksan_verify(eng)


@pytest.mark.slow   # tier-1 budget (R010): spec engine compiles draft+verify programs
def test_blocksan_clean_across_spec_and_chunked_composition(model):
    """Rejected spec drafts and chunked prefill write next to shared
    blocks every tick — the checksums prove they never write INTO
    them, on the real composition paths."""
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt3_tiny
    paddle.seed(1)
    draft = GPTForCausalLM(gpt3_tiny())
    draft.eval()
    for kw in (dict(prefix_cache=True, prefill_chunk=8),
               dict(prefix_cache=True, spec_decode=True,
                    draft_model=draft, spec_k=3)):
        with flag_guard(enable_jaxsan=False):
            _, plain = _drained_engine(model, **kw)
        with flag_guard(enable_jaxsan=True):
            eng, sanitized = _drained_engine(model, **kw)
        assert sanitized == plain, kw
        assert eng._blocksan.verifies > 0


# ============================== --changed mode (ISSUE 12 satellite)

def test_changed_paths_refuses_bad_ref():
    from paddle_tpu.tooling.analyze.__main__ import changed_paths
    with pytest.raises(RuntimeError, match="git"):
        changed_paths("no-such-ref-xyzzy")


@pytest.mark.slow   # tier-1 budget (R010): git + CLI subprocesses
def test_cli_changed_mode_lints_only_the_diff(tmp_path):
    """`--changed REF` is the seconds-scale incremental ratchet: only
    files differing from the ref are linted, so a violation in an
    UNCHANGED file stays the full-tree gate's business."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")

    def git(*args):
        out = subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t"]
            + list(args), capture_output=True, text=True,
            cwd=str(tmp_path), timeout=60)
        assert out.returncode == 0, out.stderr
        return out

    git("init", "-q")
    (tmp_path / "clean.py").write_text("x = 1\n")
    (tmp_path / "old_violation.py").write_text(R001_BAD)
    git("add", "-A")
    git("commit", "-qm", "base")
    (tmp_path / "changed.py").write_text(R003_BAD)      # untracked

    # run the CLI from the tmp repo: __main__.changed_paths anchors at
    # the PACKAGE repo, so exercise the library path directly here
    from paddle_tpu.tooling.analyze import analyze_paths as ap
    diff = subprocess.run(
        ["git", "diff", "--name-only", "HEAD", "--", "*.py"],
        capture_output=True, text=True, cwd=str(tmp_path), timeout=60)
    untracked = subprocess.run(
        ["git", "ls-files", "--others", "--exclude-standard", "--",
         "*.py"], capture_output=True, text=True, cwd=str(tmp_path),
        timeout=60)
    changed = sorted(set(diff.stdout.split())
                     | set(untracked.stdout.split()))
    assert changed == ["changed.py"]
    fs = ap([str(tmp_path / f) for f in changed], root=str(tmp_path))
    assert rules_of(fs) == ["R003"]          # old_violation.py unseen

    # and the real CLI end-to-end on the package repo: HEAD-diff mode
    # runs in seconds and exits honestly
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tooling.analyze",
         "--changed", "HEAD"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert out.returncode in (0, 1), out.stdout + out.stderr
    assert "graft-lint" in out.stdout
    bad = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.tooling.analyze",
         "--changed", "no-such-ref-xyzzy"],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=120)
    assert bad.returncode == 2


def test_r007_raise_inside_releasing_try_is_clean(tmp_path):
    """A `raise` inside a try whose handler releases the family is a
    covered unwind, not a leak (review fix: the Raise branch consults
    the same `protected` set as the dispatch exception edge)."""
    src = R007_BAD_RETURN.replace(
        "        if not req.ok:\n            return False\n",
        "        try:\n"
        "            if not req.ok:\n"
        "                raise ValueError(\"bad\")\n"
        "        except ValueError:\n"
        "            self._release_block(blk)\n"
        "            raise\n")
    assert run_src(tmp_path, {"mod.py": src}, rules=["R007"]) == []

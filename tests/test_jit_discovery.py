"""to_static's state discovery: the traced pass finds the state the eager
record finds, builds the same program, and leaves nothing traced behind
— also when the function breaks the graph half-way through."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import amp, nn, optimizer
from paddle_tpu.framework import random as _random
from paddle_tpu.jit import api as jit_api
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import metrics


def _batches(n=2):
    rs = np.random.RandomState(0)
    return [tuple(paddle.to_tensor(rs.randint(0, 64, (2, 16)).astype(np.int32))
                  for _ in range(2)) for _ in range(n)]


def _gpt(level="O1"):
    paddle.seed(11)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                    max_seq_len=16)
    model = GPTForCausalLM(cfg)
    model.train()
    opt = optimizer.AdamW(learning_rate=1e-2, parameters=model.parameters())
    if level == "O2":   # bf16 parameters, fp32 master weights
        model, opt = amp.decorate(model, opt, level="O2", dtype="bfloat16")

    def gpt_step(ids, labels):
        with amp.auto_cast(True, level=level, dtype="bfloat16"):
            loss = model.compute_loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return gpt_step, model, opt, _batches()


def _batchnorm():
    paddle.seed(11)
    net = nn.Sequential(nn.Linear(4, 4), nn.BatchNorm1D(4))
    x = paddle.to_tensor(np.random.RandomState(0).randn(8, 4)
                         .astype(np.float32))

    def bn_step(x):
        return net(x)

    return bn_step, net, None, [(x,)]


def _lr_schedule():
    sched = optimizer.lr.StepDecay(0.1, step_size=1, gamma=0.1)
    p = paddle.Parameter(np.ones(1, np.float32))
    opt = optimizer.SGD(learning_rate=sched, parameters=[p])

    def lr_step(x):
        (p * x).sum().backward()
        opt.step()
        opt.clear_grad()
        return p * 1.0

    return lr_step, None, opt, [(paddle.ones([1]),)]


_CASES = {"gpt_adamw_o1": _gpt, "gpt_adamw_o2_master": lambda: _gpt("O2"),
          "batchnorm_running_stats": _batchnorm,
          "lr_schedule": _lr_schedule}


def _eager_record(sf, args, kwargs):
    slots, changed, _ = jit_api.StaticFunction._discover_state(
        sf, args, kwargs, sot_record=True)
    return slots, changed


def _traced(sf, args, kwargs):
    slots, changed, burned = jit_api.StaticFunction._discover_state(
        sf, args, kwargs)
    assert burned is None
    return slots, changed


def _describe(slots, changed, opt):
    """Slots as comparable tuples: tensors by identity, optimizer state
    by store name and key with the entry's shape, dtype and placement."""
    names = {id(s): n for n, s in opt._accumulators.items()} if opt else {}
    out = []
    for s, c in zip(slots, changed):
        if isinstance(s, jit_api._TensorSlot):
            out.append(("tensor", id(s.ref()), c))
        elif isinstance(s, jit_api._DictSlot):
            v = s.get()
            assert not isinstance(v, jax.core.Tracer)
            out.append(("state", names[id(s.store)], s.key, v.shape,
                        str(v.dtype), v.sharding, c))
        else:
            out.append((type(s).__name__, c))
    return out


def _clear_state(opt):
    """Back to a never-stepped optimizer, keeping the store dicts."""
    if opt is not None:
        for store in opt._accumulators.values():
            store.clear()


def _tensors(model, opt):
    ts = list(model.parameters()) + list(model.buffers()) if model else []
    if opt is not None:
        ts += list(opt._parameter_list)
    return ts


def _assert_nothing_traced(model, opt):
    for t in _tensors(model, opt):
        assert not isinstance(t._value, jax.core.Tracer)
        if t._grad is not None:
            assert not isinstance(t._grad._value, jax.core.Tracer)
    if opt is not None:
        assert not isinstance(opt._global_step, jax.core.Tracer)
        assert opt._lr_override is None
        for store in opt._accumulators.values():
            for v in store.values():
                assert not isinstance(v, jax.core.Tracer)


def _rng_bits():
    return np.asarray(jax.random.key_data(_random.get_rng_state()))


@pytest.mark.parametrize("case", list(_CASES))
def test_traced_discovery_finds_the_eager_records_slots(case):
    fn, model, opt, batches = _CASES[case]()
    sf = paddle.jit.to_static(fn)
    rng0 = _rng_bits()
    traced = _describe(*_traced(sf, batches[0], {}), opt)
    _assert_nothing_traced(model, opt)
    np.testing.assert_array_equal(_rng_bits(), rng0)
    _clear_state(opt)
    eager = _describe(*_eager_record(sf, batches[0], {}), opt)
    assert traced == eager
    assert any(c for *_, c in traced) or case == "lr_schedule"


def _first_lowering_and_outputs(case, record, steps=10):
    """The first build's lowered program text and `steps` calls'
    outputs, with the state discovered by `record`."""
    fn, model, opt, batches = _CASES[case]()
    sf = paddle.jit.to_static(fn)
    sf._discover_state = lambda a, k, sot_record=False: (
        *record(sf, a, k), None)
    texts = []
    build = sf._build

    def lowering_build(*a, **k):
        prog = build(*a, **k)
        jitted = prog["jitted"]

        def call(*args):
            if prog.get("fresh"):
                texts.append(jitted.lower(*args).as_text())
            return jitted(*args)
        prog["jitted"] = call
        return prog

    sf._build = lowering_build
    outs = [np.asarray(sf(*batches[i % len(batches)])._value)
            for i in range(steps)]
    return texts, outs


@pytest.mark.parametrize("case", ["gpt_adamw_o1", "batchnorm_running_stats",
                                  "lr_schedule"])
def test_traced_discovery_builds_the_same_program(case):
    texts_t, outs_t = _first_lowering_and_outputs(case, _traced)
    texts_e, outs_e = _first_lowering_and_outputs(case, _eager_record)
    assert len(texts_t) == len(texts_e) == 1
    assert texts_t == texts_e
    for a, b in zip(outs_t, outs_e):
        np.testing.assert_array_equal(a, b)


def _breaks_after_the_update():
    fn, model, opt, batches = _gpt()

    def step_then_host_read(ids, labels):
        loss = fn(ids, labels)
        loss_host = float(loss)    # a host read of a traced value
        return loss * (loss_host > 0)

    return step_then_host_read, model, opt, batches


def _grad_scaler():
    paddle.seed(11)
    fn, model, opt, batches = _gpt()
    scaler = amp.GradScaler(init_loss_scaling=1024.0)

    def scaled_step(ids, labels):
        with amp.auto_cast(True, level="O1", dtype="bfloat16"):
            loss = model.compute_loss(ids, labels)
        scaler.scale(loss).backward()
        scaler.step(opt)     # under a trace: the host found_inf read breaks
        scaler.update()
        opt.clear_grad()
        return loss

    return scaled_step, model, opt, batches


@pytest.mark.parametrize("case", ["builds", "breaks_after_the_update",
                                  "grad_scaler_breaks"])
def test_discovery_leaves_nothing_traced(case):
    make = {"builds": _gpt, "breaks_after_the_update":
            _breaks_after_the_update, "grad_scaler_breaks": _grad_scaler}[case]
    fn, model, opt, batches = make()
    params0 = {k: np.asarray(v._value) for k, v in model.state_dict().items()}
    rng0 = _rng_bits()
    sf = paddle.jit.to_static(fn)
    if case == "builds":
        sf._build(batches[0], {})
    else:
        with pytest.raises(sf._graph_break_errors):
            sf._discover_state(batches[0], {})
        assert all(not store for store in opt._accumulators.values())
    _assert_nothing_traced(model, opt)
    np.testing.assert_array_equal(_rng_bits(), rng0)
    assert opt._global_step == 0
    for k, v in model.state_dict().items():
        np.testing.assert_array_equal(np.asarray(v._value), params0[k])
    # a later eager call starts from where a fresh model does
    loss = float(fn(*batches[0]))
    fresh_fn, *_ = make()
    assert loss == float(fresh_fn(*batches[0]))


def test_discovery_restores_an_accumulated_grad():
    """A backward accumulates into a grad already there, in place: the
    pass must put that grad's value back, not only the grad object."""
    q = paddle.Parameter(np.ones(2, np.float32))

    def fwd_bwd(x):
        (q * x).sum().backward()
        return x

    sf = paddle.jit.to_static(fwd_bwd)
    sf(paddle.to_tensor([2.0, 3.0]))
    grad = q.grad
    sf._discover_state((paddle.to_tensor([[1.0, 1.0]]),), {})
    assert q.grad is grad
    assert not isinstance(grad._value, jax.core.Tracer)
    np.testing.assert_array_equal(grad.numpy(), [2.0, 3.0])


def _discoveries(name, mode):
    c = metrics.get("jit.discovery")
    return c.value(fn=name, mode=mode) if c is not None else 0


def test_discovery_mode_is_counted():
    fn, _, _, batches = _gpt()
    sf = paddle.jit.to_static(fn)
    before = _discoveries("gpt_step", "trace")
    sf(*batches[0])
    assert _discoveries("gpt_step", "trace") == before + 1

    def burn_scale(x, s):
        return x * float(s)        # SOT burns the value: an eager record

    sb = paddle.jit.to_static(burn_scale)
    n_trace = _discoveries("burn_scale", "trace")
    n_eager = _discoveries("burn_scale", "eager")
    out = sb(paddle.to_tensor([3.0]), paddle.to_tensor(2.0))
    np.testing.assert_allclose(out.numpy(), [6.0])
    assert sb._stats["sot_specializations"] == 1
    assert _discoveries("burn_scale", "trace") == n_trace + 1
    assert _discoveries("burn_scale", "eager") == n_eager + 1


@pytest.mark.parametrize("offload", [False, True])
def test_fresh_sharded_state_is_placed_like_the_eager_record(hybrid_mesh,
                                                              offload):
    """ZeRO stage 2 makes fresh state through its wrapped creation path:
    sharded over the axis, and in host memory under offload, as the
    state lives between steps."""
    from paddle_tpu.distributed.fleet.sharding import (
        GroupShardedOptimizerStage2)
    lin = nn.Linear(4, 4)
    opt = optimizer.Adam(learning_rate=1e-3, parameters=lin.parameters())
    sharded = GroupShardedOptimizerStage2(lin.parameters(), opt,
                                          offload=offload)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))

    def zero_step(xb):
        loss = (lin(xb) * lin(xb)).sum()
        loss.backward()
        sharded.step()
        sharded.clear_grad()
        return loss

    sf = paddle.jit.to_static(zero_step)
    traced = _describe(*_traced(sf, (x,), {}), opt)
    _assert_nothing_traced(lin, opt)
    _clear_state(opt)
    eager = _describe(*_eager_record(sf, (x,), {}), opt)
    assert traced == eager
    shardings = [e[5] for e in traced if e[0] == "state"]
    assert any("sharding" in str(s.spec) for s in shardings
               if hasattr(s, "spec"))
    kinds = {m.kind for m in jax.local_devices()[0].addressable_memories()}
    if offload and "pinned_host" in kinds:
        assert {s.memory_kind for s in shardings} == {"pinned_host"}

"""`benchmark/reducers/program_spans.py` by hand, on traces built with
`xplane.from_events` (times in ns): idle time split between two spans,
module means, rooflines from given attrs, scopes, and None wherever there
is no device plane or the program has no such span."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.reducers import program_spans as ps  # noqa: E402
from benchmark.reducers import xplane  # noqa: E402

MS = 1_000_000
CFG = {"num_layers": 24, "hidden_size": 2048}
CALL = ' = bf16[8,16,128]{2,1,0} custom-call(%a), ' \
    'custom_call_target="tpu_custom_call"'


def _serve_trace():
    """A window of 500 ms; the device idles in (100, 200) and (300, 400)."""
    ops = [(0, 100 * MS, "%fusion.1 = f32[] fusion()"),
           (200 * MS, 300 * MS, "%paged_decode.3" + CALL),
           (400 * MS, 440 * MS, "%paged_chunk_prefill.7" + CALL),
           (440 * MS, 500 * MS, "%fusion.2 = f32[] fusion()")]
    modules = [(-10 * MS, 100 * MS, "jit_serving_tick(11)"),   # cut: left out
               (200 * MS, 270 * MS, "jit_serving_tick(11)"),
               (400 * MS, 474 * MS, "jit_serving_tick(11)"),
               (270 * MS, 300 * MS, "jit_serving_prefill_cont(5)")]
    host = [(0, 500 * MS, xplane.WINDOW),
            (90 * MS, 210 * MS, "serve:harvest_wait"),
            (290 * MS, 350 * MS, "serve:schedule"),
            (310 * MS, 340 * MS, "serve:chunk_dispatch"),   # nested
            (350 * MS, 380 * MS, "serve:tick_dispatch"),
            (380 * MS, 390 * MS, "np.asarray(jax.Array)"),
            (450 * MS, 460 * MS, "serve:tick_dispatch")]
    return xplane.from_events({0: {"ops": ops, "modules": modules}}, host)


HOST_ARGS = {"spans": ["serve:schedule", "serve:prefill_dispatch",
                       "serve:chunk_dispatch", "serve:tick_dispatch",
                       "serve:emit"], "per": "serve:tick_dispatch"}


def test_idle_time_is_split_between_the_spans_that_cover_it():
    t = _serve_trace()
    assert ps.idle_gaps_of(t) == [(100 * MS, 200 * MS), (300 * MS, 400 * MS)]
    # the first gap lies under the harvest's wait, 80 of the second's 100
    # ms under schedule (with its nested chunk) and the tick's dispatch;
    # two ticks were dispatched in the window
    assert ps.idle_under_spans_ms(
        t, {}, {"spans": ["serve:harvest_wait"],
                "per": "serve:tick_dispatch"}) == pytest.approx(50.0)
    assert ps.idle_under_spans_ms(t, {}, HOST_ARGS) == pytest.approx(40.0)
    # the accepted reader charges the same gaps to the same spans by name
    gaps = dict(xplane.idle_gaps(t))
    assert gaps["serve:harvest_wait"] == pytest.approx(0.1)


def test_module_mean_leaves_out_a_launch_the_window_cuts():
    t = _serve_trace()
    assert ps.module_device_ms(
        t, {}, {"module": "jit_serving_tick"}) == pytest.approx(72.0)
    assert ps.module_device_ms(
        t, {}, {"module": "jit_serving_prefill_cont"}) == pytest.approx(30.0)
    assert ps.module_device_ms(t, {}, {"module": "jit_serving_cow"}) is None


def test_device_ms_per_step_joins_a_launch_to_its_dispatch(monkeypatch):
    # the launch at 200 ms was dispatched before the window opened, by a
    # span of 4 steps; the one at 400 ms by the span at 350 ms, 1 step;
    # the span at 450 ms launched nothing the window holds whole
    t = _serve_trace()
    spans = [(-40 * MS, -30 * MS, "serve:tick_dispatch", {"steps": 4}),
             (-20 * MS, -10 * MS, "serve:tick_dispatch", {"steps": 4}),
             (350 * MS, 380 * MS, "serve:tick_dispatch", {"steps": 1}),
             (450 * MS, 460 * MS, "serve:tick_dispatch", {"steps": 4})]
    monkeypatch.setattr(ps, "spans_with_attrs",
                        lambda trace, in_window=True: spans)
    args = {"module": "jit_serving_tick", "span": "serve:tick_dispatch"}
    assert ps.device_ms_per_step(t, {}, args) == pytest.approx(144 / 5)
    # a program whose spans carry no steps (the parent): nothing to read
    monkeypatch.setattr(ps, "spans_with_attrs",
                        lambda trace, in_window=True: [])
    assert ps.device_ms_per_step(t, {}, args) is None
    assert ps.device_ms_per_step(None, {}, args) is None


def test_work_of_the_paged_kernels_from_the_spans_attrs():
    # a tick of 4 steps over 2 slots holding 100 tokens reads 100 + 2,
    # 100 + 4, 100 + 6, 100 + 8 tokens; a token is 2 * 24 * 2048 bf16
    ticks = [{"steps": 4, "active": 2, "kv_tokens": 100}]
    assert ps.decode_kv_bytes(ticks, CFG) == 420 * 2 * 24 * 2048 * 2
    # a chunk of 4 queries ending a context of 10: 4 * 6 + 10 pairs, two
    # matmuls of 2 * 2048 a pair a layer; K, V of 10 and q, o of 4 tokens
    flops, nbytes = ps.chunk_work([{"q_tokens": 4, "kv_tokens": 10}], CFG)
    assert flops == 4.0 * 34 * 24 * 2048
    assert nbytes == (2 * 10 + 2 * 4) * 24 * 2048 * 2


def test_paged_rooflines_from_given_attrs(monkeypatch):
    t = _serve_trace()
    spans = [(350 * MS, 380 * MS, "serve:tick_dispatch",
              {"steps": 4, "active": 2, "kv_tokens": 100}),
             (310 * MS, 340 * MS, "serve:chunk_dispatch",
              {"rid": 1, "q_tokens": 256, "kv_tokens": 512}),
             (0, 1, "serve:tick_dispatch", {"active": 2})]    # no steps: out
    monkeypatch.setattr(ps, "spans_with_attrs",
                        lambda trace, in_window=True: spans)
    monkeypatch.setattr(ps, "_peaks", lambda: (197e12, 819e9))
    args = {"pattern": "paged_decode", "config": "gpt3-1p3b"}
    least = 420 * 2 * 24 * 2048 * 2 / 819e9
    assert ps.paged_decode_roofline_pct(t, {}, args) == pytest.approx(
        100 * least / 0.1)
    flops, nbytes = ps.chunk_work([spans[1][3]], CFG)
    assert nbytes / 819e9 > flops / 197e12           # bytes bind here
    assert ps.paged_chunk_roofline_pct(
        t, {}, {"pattern": "paged_chunk_prefill", "config": "gpt3-1p3b"}) \
        == pytest.approx(100 * (nbytes / 819e9) / 0.04)
    # a rehearsal's CPU has no peak; a program without the attrs no spans
    monkeypatch.setattr(ps, "_peaks", lambda: None)
    assert ps.paged_decode_roofline_pct(t, {}, args) is None
    monkeypatch.setattr(ps, "spans_with_attrs", lambda trace: [])
    monkeypatch.setattr(ps, "_peaks", lambda: (197e12, 819e9))
    assert ps.paged_decode_roofline_pct(t, {}, args) is None


def test_flash_rooflines_split_the_attention_flops():
    call = ' = bf16[1,16,2048,64]{3,2,1,0} custom-call(%q), ' \
        'custom_call_target="tpu_custom_call"'
    ops = [(0, 6 * MS, "%jvp_flash_fwd_.1" + call),
           (6 * MS, 12 * MS, "%transpose_jvp_flash_bwd_dq__.1" + call),
           (12 * MS, 20 * MS, "%transpose_jvp_flash_bwd_dkv__.1" + call),
           (20 * MS, 60 * MS, "%fusion.1 = f32[] fusion(%flash_fwd_out)")]
    t = xplane.from_events({0: {"ops": ops, "modules": []}}, [])
    c = {"traced_steps": 1, "attn_flops_per_step": 6e9, "peak_flops": 1e12}
    fwd = ps.flash_roofline_pct(t, c, {"pattern": "flash_fwd",
                                       "share": 1 / 3})
    bwd = ps.flash_roofline_pct(t, c, {"pattern": "flash_bwd_(dq|dkv)",
                                       "share": 2 / 3})
    assert fwd == pytest.approx(100 * 2e-3 / 6e-3)
    assert bwd == pytest.approx(100 * 4e-3 / 14e-3)
    # together they divide by what the accepted metric divides by
    whole = xplane.attn_kernel_roofline_pct(
        t, c, {"pattern": 'custom_call_target="tpu_custom_call"'})
    assert whole == pytest.approx(100 * 6e-3 / 20e-3)
    assert ps.kernel_s(t, "flash_fwd") + ps.kernel_s(
        t, "flash_bwd_(dq|dkv)") == pytest.approx(20e-3)
    assert ps.flash_roofline_pct(t, {}, {"pattern": "flash_fwd",
                                         "share": 1 / 3}) is None


def test_span_totals_are_read_in_process():
    from paddle_tpu import observability as obs
    obs._SPAN_TOTALS.clear()
    assert ps.span_total_s(None, {}, {"span": "to_static:discover"}) is None
    with obs.span("to_static:discover", fn="f"):
        pass
    with obs.span("to_static:discover", fn="g"):
        pass
    got = ps.span_total_s(None, {}, {"span": "to_static:discover"})
    assert got == obs.span_totals()["to_static:discover"]["total_s"] > 0


@pytest.mark.parametrize("reader, args", [
    (ps.module_device_ms, {"module": "jit_serving_tick"}),
    (ps.device_ms_per_step, {"module": "jit_serving_tick",
                             "span": "serve:tick_dispatch"}),
    (ps.idle_under_spans_ms, HOST_ARGS),
    (ps.paged_decode_roofline_pct,
     {"pattern": "paged_decode", "config": "gpt3-1p3b"}),
    (ps.paged_chunk_roofline_pct,
     {"pattern": "paged_chunk_prefill", "config": "gpt3-1p3b"}),
    (ps.flash_roofline_pct, {"pattern": "flash_fwd", "share": 1 / 3}),
    (ps.scope_device_ms, {"scope": "forward"}),
])
def test_none_without_a_device_plane(reader, args):
    counters = {"traced_steps": 3, "attn_flops_per_step": 1e9,
                "peak_flops": 1e12}
    host_only = xplane.from_events({}, [(0, 9, "serve:tick_dispatch")])
    assert reader(None, counters, args) is None
    assert reader(host_only, counters, args) is None


def test_a_program_without_the_spans_reads_none():
    """The parent of the PR that added the spans runs these readers too."""
    ops = [(0, 100 * MS, "%fusion.1 = f32[] fusion()")]
    t = xplane.from_events(
        {0: {"ops": ops, "modules": [(0, 100 * MS, "jit_tick(3)")]}},
        [(0, 200 * MS, xplane.WINDOW),
         (100 * MS, 200 * MS, "np.asarray(jax.Array)")])
    assert ps.idle_under_spans_ms(t, {}, HOST_ARGS) is None
    assert ps.module_device_ms(t, {}, {"module": "jit_serving_tick"}) is None
    assert ps.paged_decode_roofline_pct(
        t, {}, {"pattern": "paged_decode", "config": "gpt3-1p3b"}) is None


# --------------------------------------------------------------- scopes

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        n, low = n >> 7, n & 0x7F
        out.append(low | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message from `(field, int | bytes)` pairs."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def _xspace(ops: dict) -> bytes:
    """An XSpace whose `/device:TPU:0` plane holds `ops` (HLO text ->
    tf_op, None for an op without one) as event metadata, a line to
    skip, and a host plane before it."""
    stat_names = [_msg((1, 7), (2, _msg((1, 7), (2, b"hlo_category")))),
                  _msg((1, 26), (2, _msg((1, 26), (2, b"tf_op"))))]
    events = []
    for i, (text, tf_op) in enumerate(ops.items(), 1):
        stats = [(5, _msg((1, 7), (5, b"fusion")))]
        if tf_op is not None:
            stats.append((5, _msg((1, 26), (5, tf_op.encode()))))
        events.append(_msg((1, i), (2, _msg((1, i), (2, text.encode()),
                                            (4, b"short"), *stats))))
    device = _msg((1, 1), (2, b"/device:TPU:0"), (3, b"\x08\x01" * 50),
                  *[(4, e) for e in events], *[(5, s) for s in stat_names])
    return _msg((1, _msg((1, 0), (2, b"/host:CPU"))), (1, device))


def test_op_scopes_are_read_from_the_planes_event_metadata(tmp_path):
    ops = {"%fusion.1 = f32[8]{0} fusion(%p)":
           "jit(train_step)/forward/jvp()/dot_general:",
           "%copy-done.3 = f32[8]{0} copy-done(%c)": None,
           "%fusion.9 = f32[8]{0} fusion(%q)":
           "jit(train_step)/optimizer_step/jit(program)/mul:"}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(ops))
    got = ps._op_scopes(str(path), 0.0)
    assert got == {k: v or "" for k, v in ops.items()}
    assert [ps.scope_of(v) for v in got.values()] \
        == ["forward", "", "optimizer_step"]
    assert ps.scope_of("jit(train_step)/reduce_sum:") == ""
    # a file jax's own reader takes too (the bytes are a real XSpace)
    from jax.profiler import ProfileData
    assert [p.name for p in ProfileData.from_file(str(path)).planes] \
        == ["/host:CPU", "/device:TPU:0"]


def test_wire_reader_agrees_with_jaxs_on_a_trace_this_jax_wrote(tmp_path):
    # the field numbers are hard-coded; hold them to the installed
    # profiler: every plane and every event name `ProfileData` shows has
    # to be in what `plane_metadata` reads from the same bytes
    import glob
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("serve:tick_dispatch", steps=4):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    theirs = jax.profiler.ProfileData.from_file(path)
    ours = ps.plane_metadata(path)
    assert sorted(ours) == sorted(p.name for p in theirs.planes)
    for plane in theirs.planes:
        names = {e.name for ln in plane.lines for e in ln.events}
        assert names <= {n for n, _ in ours[plane.name]}, plane.name
    assert "serve:tick_dispatch" in {n for n, _ in ours["/host:CPU"]}


def test_a_device_plane_whose_ops_carry_no_tf_op_raises(tmp_path):
    # the reader must not fall silent when the profiler's format moves:
    # a device plane is there and no op of it has a scope to read
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace({"%fusion.1 = f32[8]{0} fusion(%p)": None,
                              "%copy.2 = f32[8]{0} copy(%q)": None}))
    with pytest.raises(RuntimeError, match="tf_op"):
        ps._op_scopes(str(path), 0.0)
    # no device plane (a rehearsal on the CPU): nothing to read, no fault
    host_only = tmp_path / "h.xplane.pb"
    host_only.write_bytes(_msg((1, _msg((1, 0), (2, b"/host:CPU")))))
    assert ps._op_scopes(str(host_only), 0.0) == {}


def test_own_time_by_scope_leaves_compiler_made_ops_unscoped():
    tf_ops = {"%f": "jit(s)/forward/add:", "%w": "jit(s)/forward/while:",
              "%b": "jit(s)/backward/mul:", "%c": "", "%o":
              "jit(s)/optimizer_step/jit(program)/mul:",
              "%r": "jit(s)/reduce_sum:"}
    ops = [(0, 10, "%f"), (10, 14, "%c"),          # a wait after forward
           (14, 40, "%w"), (20, 30, "%b"),         # a child of the while
           (40, 45, "%c"), (45, 50, "%o"), (50, 52, "%r")]
    got = ps.own_ns_by_scope(ops, tf_ops)
    # forward: 10 + the while's own 16; the waits and the op under no
    # scope are nobody's: a scope reads only the ops that carry it
    assert got == {"forward": 26, "backward": 10, "optimizer_step": 5,
                   "": 11}
    assert sum(got.values()) == 52


def test_scope_device_ms_on_a_trace_file(tmp_path, monkeypatch):
    tf_ops = {"%f = f32[] fusion()": "jit(s)/forward/add:",
              "%c = f32[] copy-done()": None,
              "%b = f32[] fusion()": "jit(s)/backward/mul:"}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(tf_ops))
    monkeypatch.setattr(ps, "_newest_pb", lambda: str(path))
    ops = [(0, 6 * MS, "%f = f32[] fusion()"),
           (6 * MS, 8 * MS, "%c = f32[] copy-done()"),
           (8 * MS, 20 * MS, "%b = f32[] fusion()")]
    t = xplane.from_events({0: {"ops": ops, "modules": []}}, [])
    c = {"traced_steps": 2}
    assert ps.scope_device_ms(t, c, {"scope": "forward"}) \
        == pytest.approx(3.0)
    assert ps.scope_device_ms(t, c, {"scope": "backward"}) \
        == pytest.approx(6.0)
    assert ps.scope_device_ms(t, c, {"scope": ""}) == pytest.approx(1.0)
    assert ps.scope_device_ms(t, c, {"scope": "optimizer_step"}) is None
    assert ps.scope_device_ms(t, {}, {"scope": "forward"}) is None

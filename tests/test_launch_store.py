"""TCPStore (C++ + Python fallback), launch CLI, elastic restart.

Mirrors the reference's `test/legacy_test/test_tcp_store.py` and
`test/collective/fleet/test_fleet_launch*.sh` strategies: the launch test
trains a data-parallel linear regression across 2 spawned processes with
store-based gradient allreduce and checks exact parity with the
single-process full-batch run.
"""

import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed.store import TCPStore, _PyServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _exercise_store(server_store, client):
    server_store.set("k", b"v1")
    assert client.get("k") == b"v1"
    assert not client.check("missing")
    assert client.add("ctr", 2) == 2
    assert server_store.add("ctr", 40) == 42

    def later():
        time.sleep(0.15)
        client.set("late", b"yes")

    t = threading.Thread(target=later)
    t.start()
    server_store.wait("late")
    assert server_store.get("late") == b"yes"
    t.join()

    res = []
    ts = [threading.Thread(target=lambda s=s: (s.barrier("b"),
                                               res.append(1)))
          for s in (server_store, client)]
    for x in ts:
        x.start()
    for x in ts:
        x.join(5)
    assert res == [1, 1]


def test_tcp_store_native():
    s = TCPStore(is_master=True, world_size=2)
    if not s.is_native:
        pytest.skip("no C++ toolchain in this environment")
    c = TCPStore(port=s.port, world_size=2)
    _exercise_store(s, c)


def test_tcp_store_python_fallback(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_DISABLE_NATIVE", "1")
    s = TCPStore(is_master=True, world_size=2)
    assert not s.is_native
    assert isinstance(s._server, _PyServer)
    c = TCPStore(port=s.port, world_size=2)
    _exercise_store(s, c)


def test_store_wait_timeout_and_reconnect():
    s = TCPStore(is_master=True)
    with pytest.raises(TimeoutError):
        s.wait("never-set", timeout=0.3)
    # connection was dropped and must transparently re-establish
    s.set("after", b"ok")
    assert s.get("after") == b"ok"


def test_store_delete_key():
    s = TCPStore(is_master=True)
    s.set("tmp", b"payload")
    assert s.check("tmp")
    s.delete_key("tmp")
    assert not s.check("tmp")
    s.delete_key("never-existed")  # idempotent


def test_store_per_thread_connections_dont_block():
    """A thread parked in wait() must not block another thread's set()."""
    s = TCPStore(is_master=True)
    got = []

    def waiter():
        s.wait("signal", timeout=10)
        got.append(s.get("signal"))

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.2)
    s.set("signal", b"go")  # same TCPStore object, different thread
    t.join(5)
    assert got == [b"go"]


def test_store_cross_process():
    s = TCPStore(is_master=True, world_size=1)
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        from paddle_tpu.distributed.store import TCPStore
        c = TCPStore(port={s.port})
        c.set("from_child", b"hi")
        print(c.add("shared", 10))
    """)
    # graft-lint: disable=R010 (one -c child, no jax import; ~1.4s measured)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "10"
    assert s.get("from_child") == b"hi"
    assert s.add("shared", 1) == 11


DP_SCRIPT = r"""
import json, os, pickle, sys
sys.path.insert(0, os.environ["REPO_DIR"])
# pin the workers to CPU in the script itself: two workers must never
# both open the host's accelerator (a chip belongs to one process)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu.distributed as dist

rank = int(os.environ["PADDLE_TRAINER_ID"])
world = int(os.environ["PADDLE_TRAINERS_NUM"])

# data-parallel linear regression: full batch split by rank
rng = np.random.RandomState(0)
X = rng.randn(8, 3).astype(np.float32)
yt = X @ np.array([1.0, -2.0, 0.5], np.float32)
w = np.zeros(3, np.float32)
shard = X[rank::world], yt[rank::world]

store = dist.collective._host_store()
assert store is not None
for step in range(3):
    xb, yb = shard
    g_local = 2 * xb.T @ (xb @ w - yb) / len(X)
    # store-based gradient allreduce (control-plane path; ICI collectives
    # are exercised by the SPMD tests)
    store.set(f"grad/{step}/{rank}", pickle.dumps(g_local))
    total = np.zeros_like(w)
    for r in range(world):
        store.wait(f"grad/{step}/{r}")
        total += pickle.loads(store.get(f"grad/{step}/{r}"))
    w -= 0.1 * total
    dist.barrier()

# p2p smoke test through the host path
import paddle_tpu as paddle
if rank == 0:
    dist.send(paddle.to_tensor(w), dst=1)
else:
    t = paddle.to_tensor(np.zeros(3, np.float32))
    dist.recv(t, src=0)
    np.testing.assert_allclose(np.asarray(t._value), w, rtol=1e-6)

out = os.path.join(os.environ["OUT_DIR"], f"rank{rank}.json")
with open(out, "w") as f:
    json.dump({"w": w.tolist()}, f)
"""


@pytest.mark.slow   # tier-1 budget (R010): 2-proc jax children, ~4s
def test_launch_two_process_dp_parity(tmp_path):
    script = tmp_path / "train_dp.py"
    script.write_text(DP_SCRIPT)
    env = dict(os.environ)
    env.update({"REPO_DIR": REPO, "OUT_DIR": str(tmp_path),
                "JAX_PLATFORMS": "cpu"})
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(tmp_path / "log"),
         "--job_id", "dptest", str(script)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    logs = ""
    logdir = tmp_path / "log"
    if logdir.exists():
        for f in sorted(logdir.iterdir()):
            logs += f"\n--- {f.name}\n" + f.read_text()[-2000:]
    assert proc.returncode == 0, proc.stderr + logs

    # per-rank logs exist
    assert (logdir / "dptest.rank0.log").exists()
    assert (logdir / "dptest.rank1.log").exists()

    # both ranks converged to the same weights as the serial full batch
    import json
    w0 = json.load(open(tmp_path / "rank0.json"))["w"]
    w1 = json.load(open(tmp_path / "rank1.json"))["w"]
    np.testing.assert_allclose(w0, w1, rtol=1e-6)

    rng = np.random.RandomState(0)
    X = rng.randn(8, 3).astype(np.float32)
    yt = X @ np.array([1.0, -2.0, 0.5], np.float32)
    w = np.zeros(3, np.float32)
    for _ in range(3):
        w -= 0.1 * (2 * X.T @ (X @ w - yt) / len(X))
    np.testing.assert_allclose(w0, w, rtol=1e-5)


FLAKY_SCRIPT = r"""
import os, sys
flag = os.path.join(os.environ["OUT_DIR"], "attempted")
if not os.path.exists(flag):
    open(flag, "w").close()
    sys.exit(3)  # first generation dies
sys.exit(0)
"""


def test_launch_elastic_restart(tmp_path):
    script = tmp_path / "flaky.py"
    script.write_text(FLAKY_SCRIPT)
    env = dict(os.environ)
    env.update({"OUT_DIR": str(tmp_path)})
    # graft-lint: disable=R010 (jax-free flaky child; ~2s measured)
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--max_restart", "1",
         "--log_dir", str(tmp_path / "log"), str(script)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr
    assert "restart 0/1" in proc.stderr


def test_launch_failure_without_elastic(tmp_path):
    script = tmp_path / "fail.py"
    script.write_text("import sys; sys.exit(7)\n")
    # graft-lint: disable=R010 (child exits immediately; ~1.6s measured)
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--log_dir", str(tmp_path / "log"),
         str(script)],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert proc.returncode == 7


def test_elastic_manager_heartbeats():
    from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                      ElasticStatus)
    s = TCPStore(is_master=True)
    m0 = ElasticManager(s, node_id=0, nnodes=2, interval=0.1)
    m1 = ElasticManager(TCPStore(port=s.port), node_id=1, nnodes=2,
                        interval=0.1)
    m0.start()
    m1.start()
    time.sleep(0.3)
    assert m0.dead_nodes() == []
    assert m0.status() is ElasticStatus.COMPLETED
    m1.stop()
    time.sleep(0.6)
    assert m0.dead_nodes() == [1]
    assert m0.status() is ElasticStatus.RESTART
    assert m0.should_restart()
    m0.stop()
"""Note: manager watch grace is 2.5*interval=0.25s; 0.6s sleep is ample."""


def test_elastic_membership_registry_and_watch():
    """Round-3 elastic depth (ref elastic/manager.py:124): node registry
    with endpoint collection, scale-up join, membership watch callback,
    and generation-advance endpoint rewrite."""
    import threading
    import time as _time
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    from paddle_tpu.distributed.store import TCPStore

    s = TCPStore(port=0, is_master=True, world_size=1)
    try:
        m0 = ElasticManager(s, node_id=0, nnodes=2, interval=0.1,
                            min_nodes=2)
        m1 = ElasticManager(TCPStore(port=s.port), node_id=1, nnodes=2,
                            interval=0.1)
        m0.register("10.0.0.1:8000")
        m1.register("10.0.0.2:8000")
        assert m0.collect_endpoints(timeout=5) == ["10.0.0.1:8000",
                                                   "10.0.0.2:8000"]
        # membership watch fires when the roster changes
        changes = []
        ev = threading.Event()

        def on_change(dead, eps):
            changes.append((dead, eps))
            ev.set()

        stop = m0.watch(on_change, poll=0.05)
        _time.sleep(0.15)  # let the watcher take its baseline
        joiner = ElasticManager(TCPStore(port=s.port), node_id=-1,
                                nnodes=2, interval=0.1)
        new_id = joiner.join("10.0.0.3:8000")
        # ids 0 and 1 are taken by registered nodes: the joiner may NOT
        # collide with them
        assert new_id == 2
        assert m0.endpoints()[:2] == ["10.0.0.1:8000", "10.0.0.2:8000"]
        ev.wait(timeout=5)
        stop.set()
        assert changes, "watch never fired on membership change"
        # generation advance = endpoint rewrite namespace
        g = m0.next_generation()
        assert g == 1
        m0.register("10.0.0.1:9000")
        assert m0.endpoints()[0] == "10.0.0.1:9000"
    finally:
        s.stop() if hasattr(s, "stop") else None


def _ctrl_args(**kw):
    from types import SimpleNamespace
    base = dict(master=None, rank=-1, nnodes=None, nproc_per_node=1,
                log_dir="log", log_level="INFO", job_id="elastic-test",
                run_mode="collective", max_restart=0,
                elastic_timeout=10.0, training_script="x.py",
                training_script_args=[])
    base.update(kw)
    return SimpleNamespace(**base)


def test_elastic_rendezvous_settles_at_max():
    """MIN:MAX rendezvous (ISSUE 19): with both nodes present inside
    the join window, the world settles at MAX — and every node adopts
    the settled size (world_size feeds PADDLE_TRAINERS_NUM, which the
    training side's elastic-ZeRO resume re-plans against)."""
    from paddle_tpu.distributed.launch.main import CollectiveController

    c0 = CollectiveController(_ctrl_args(nnodes="1:2", rank=0))
    assert c0.elastic and c0.nnodes_min == 1 and c0.nnodes_max == 2
    done = []
    t0 = threading.Thread(target=lambda: (c0.rendezvous(),
                                          done.append(0)))
    t0.start()
    deadline = time.time() + 5
    while c0.master is None and time.time() < deadline:
        time.sleep(0.02)
    assert c0.master is not None, "node 0 never hosted the store"
    c1 = CollectiveController(_ctrl_args(nnodes="1:2", rank=1,
                                         master=c0.master))
    t1 = threading.Thread(target=lambda: (c1.rendezvous(),
                                          done.append(1)))
    t1.start()
    t0.join(15)
    t1.join(15)
    assert sorted(done) == [0, 1]
    assert c0.nnodes == 2 and c1.nnodes == 2
    assert c0.world_size == 2 and c1.world_size == 2
    assert c1.coordinator == c0.coordinator
    env = c1._worker_env(0)
    assert env["PADDLE_TRAINERS_NUM"] == "2"
    assert env["PADDLE_NNODES"] == "2"


def test_elastic_rendezvous_settles_at_min_on_timeout():
    """A lone node in a 1:3 window settles at MIN when the join window
    closes — a degraded-world resume, not a hang on the fixed-world
    barrier.  Below MIN the rendezvous must raise instead."""
    from paddle_tpu.distributed.launch.main import CollectiveController

    c = CollectiveController(_ctrl_args(nnodes="1:3", rank=0,
                                        elastic_timeout=0.4))
    c.rendezvous()
    assert c.nnodes == 1 and c.world_size == 1
    assert c._worker_env(0)["PADDLE_TRAINERS_NUM"] == "1"

    under = CollectiveController(_ctrl_args(nnodes="2:3", rank=0,
                                            elastic_timeout=0.4))
    with pytest.raises(TimeoutError, match="minimum 2"):
        under.rendezvous()


def test_non_elastic_nnodes_spec_unchanged():
    """A plain `--nnodes N` never enters the settle window: the parsed
    bounds collapse and `elastic` stays off (the legacy fixed-world
    barrier path, byte-identical behavior)."""
    from paddle_tpu.distributed.launch.main import CollectiveController

    c = CollectiveController(_ctrl_args(nnodes="2", rank=0))
    assert not c.elastic
    assert (c.nnodes_min, c.nnodes_max, c.nnodes) == (2, 2, 2)
    with pytest.raises(AssertionError):
        CollectiveController(_ctrl_args(nnodes="3:2", rank=0))


ELASTIC_RESUME_SCRIPT = r"""
import json, os, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed.checkpoint as dist_cp

out = os.environ["OUT_DIR"]
gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
ckpt = os.path.join(out, "ckpt")
TOTAL = 4

w = paddle.zeros([3], dtype="float32")
start = 0
if os.path.isdir(ckpt) and os.listdir(ckpt):
    state = {"w": w, "step": paddle.to_tensor(0)}
    dist_cp.load_state_dict(state, ckpt)
    start = int(state["step"].numpy())
    w = state["w"]

rng = np.random.RandomState(0)
X = paddle.to_tensor(rng.randn(8, 3).astype(np.float32))
yt = X @ paddle.to_tensor(np.array([1.0, -2.0, 0.5], np.float32))
for step in range(start, TOTAL):
    grad = 2 * X.T @ (X @ w - yt) / 8
    w = w - 0.1 * grad
    dist_cp.save_state_dict({"w": w, "step": paddle.to_tensor(step + 1)},
                            ckpt)
    if gen == 0 and step + 1 == 2:
        sys.exit(5)  # die mid-training; generation 1 must resume from ckpt

json.dump({"w": w.numpy().tolist(), "resumed_from": start, "gen": gen},
          open(os.path.join(out, "result.json"), "w"))
"""


@pytest.mark.slow   # tier-1 budget (R010): restarting jax child, ~5s
def test_elastic_restart_resumes_from_dist_checkpoint(tmp_path):
    """End-to-end elasticity (ref elastic/manager.py:124 semantics): a
    worker dies mid-training after step 2, the launcher restarts it in a
    new generation, and the new generation resumes from the distributed
    checkpoint rather than restarting from scratch."""
    import json
    script = tmp_path / "train.py"
    script.write_text(ELASTIC_RESUME_SCRIPT)
    env = dict(os.environ)
    env.update({"OUT_DIR": str(tmp_path), "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", "")})
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1", "--max_restart", "1",
         "--log_dir", str(tmp_path / "log"), str(script)],
        capture_output=True, text=True, timeout=240, env=env, cwd=REPO)
    logs = ""
    logdir = tmp_path / "log"
    if logdir.exists():
        for f in sorted(logdir.iterdir()):
            logs += f"\n--- {f.name}\n" + f.read_text()[-2000:]
    assert proc.returncode == 0, proc.stderr + logs
    assert "restart 0/1" in proc.stderr
    res = json.load(open(tmp_path / "result.json"))
    assert res["gen"] == 1
    assert res["resumed_from"] == 2, "generation 1 did not resume from ckpt"
    # the resumed run must land on exactly the serial 4-step weights
    rng = np.random.RandomState(0)
    X = rng.randn(8, 3).astype(np.float32)
    yt = X @ np.array([1.0, -2.0, 0.5], np.float32)
    w = np.zeros(3, np.float32)
    for _ in range(4):
        w -= 0.1 * (2 * X.T @ (X @ w - yt) / len(X))
    np.testing.assert_allclose(res["w"], w, rtol=1e-5)


# ------------------------------------------------------------- ISSUE 20
# unattended elastic training: store hardening, heartbeat leases,
# progress watchdog, late-join scale-up


def test_store_retry_absorbs_transient_fault():
    """One transient socket error inside a request is absorbed by the
    bounded retry (FLAGS_store_retries); a persistent fault still
    surfaces once the budget is spent."""
    from paddle_tpu.testing import chaos
    s = TCPStore(is_master=True)
    s.set("k", b"v")
    c = TCPStore(port=s.port)
    assert c.get("k") == b"v"   # wire the per-thread conn first
    with chaos.fail_at("store.request", on_calls=[1]) as fault:
        assert c.get("k") == b"v"
    assert fault.fires == 1
    with chaos.fail_at("store.request"):
        with pytest.raises(OSError):
            c.get("k")
    assert c.get("k") == b"v"   # transparently reconnects afterwards


def test_store_get_timeout_is_semantic_not_retried():
    """get() on a missing key parks server-side; the client timeout is
    a SEMANTIC timeout (TimeoutError, no retries — retrying would
    triple the wait and never help)."""
    s = TCPStore(is_master=True)
    t0 = time.time()
    with pytest.raises(TimeoutError):
        s.get("never-set", timeout=0.3)
    assert time.time() - t0 < 2.0  # one wait, not retries x backoff
    s.set("after", b"ok")
    assert s.get("after", timeout=5.0) == b"ok"


def _two_node_controllers(**kw):
    """Rendezvous a hosted 2-node elastic world in-process (threads)."""
    from paddle_tpu.distributed.launch.main import CollectiveController
    c0 = CollectiveController(_ctrl_args(nnodes="1:2", rank=0,
                                         elastic_timeout=3.0, **kw))
    done = []
    t0 = threading.Thread(target=lambda: (c0.rendezvous(),
                                          done.append(0)))
    t0.start()
    deadline = time.time() + 5
    while c0.master is None and time.time() < deadline:
        time.sleep(0.02)
    assert c0.master is not None, "node 0 never hosted the store"
    c1 = CollectiveController(_ctrl_args(nnodes="1:2", rank=-1,
                                         master=c0.master,
                                         elastic_timeout=3.0, **kw))
    t1 = threading.Thread(target=lambda: (c1.rendezvous(),
                                          done.append(1)))
    t1.start()
    t0.join(15)
    t1.join(15)
    assert sorted(done) == [0, 1]
    assert c0.nnodes == 2 and c1.nnodes == 2
    return c0, c1


def test_heartbeat_lease_expiry_bumps_generation():
    """Lease protocol end-to-end at store level: a silenced peer lease
    ages out after FLAGS_elastic_lease_timeout_s and the survivor
    publishes the bumped restart generation, which the other node's
    watch poll adopts."""
    from paddle_tpu import flags
    flags.set_flags({"elastic_lease_timeout_s": 0.4})
    try:
        c0, c1 = _two_node_controllers()
        gen = 0
        # join grace: freshly rendezvoused, an absent peer lease is NOT
        # death evidence yet
        assert not c0._check_peer_leases(gen)
        c0._publish_lease(gen)
        c1._publish_lease(gen)
        c0._gen_started = time.time() - 10   # age past the join grace
        assert not c0._check_peer_leases(gen)
        c1._publish_lease(gen)               # lease moved -> still alive
        assert not c0._check_peer_leases(gen)
        # silence node 1: after the timeout its lease expires
        deadline = time.time() + 5
        bumped = False
        while time.time() < deadline and not bumped:
            bumped = c0._check_peer_leases(gen)
            time.sleep(0.05)
        assert bumped, "silenced peer lease never expired"
        assert int(c0.store.get("restart_generation", timeout=5.0)) == 1
        assert c1._peer_generation() == 1    # watch() would PEER_RESTART
    finally:
        flags.set_flags({"elastic_lease_timeout_s": 5.0})


def test_chaos_silenced_lease_is_detected():
    """The ``elastic.lease.publish`` chaos site makes a LIVE node's
    heartbeat vanish — the peer must still declare it dead (the drill's
    simulated sudden death, without killing a process)."""
    from paddle_tpu import flags
    from paddle_tpu.testing import chaos
    flags.set_flags({"elastic_lease_timeout_s": 0.4})
    try:
        c0, c1 = _two_node_controllers()
        gen = 0
        c0._publish_lease(gen)
        c1._publish_lease(gen)
        c0._gen_started = time.time() - 10
        assert not c0._check_peer_leases(gen)
        with chaos.fail_at("elastic.lease.publish") as fault:
            deadline = time.time() + 5
            bumped = False
            while time.time() < deadline and not bumped:
                c1._publish_lease(gen)       # armed: publish vanishes
                bumped = c0._check_peer_leases(gen)
                time.sleep(0.05)
        assert fault.fires > 0
        assert bumped, "chaos-silenced lease never expired"
        assert int(c0.store.get("restart_generation", timeout=5.0)) == 1
    finally:
        flags.set_flags({"elastic_lease_timeout_s": 5.0})


def test_progress_watchdog_kills_stalled_worker():
    """A worker whose step heartbeat freezes past
    FLAGS_elastic_stall_timeout_s is SIGKILLed; a worker that never
    published is never armed, and so never killed."""
    from paddle_tpu import flags
    from paddle_tpu.distributed.launch.main import (CollectiveController,
                                                    Proc)
    flags.set_flags({"elastic_stall_timeout_s": 0.4})
    stalled = quiet = None
    try:
        c = CollectiveController(_ctrl_args(nnodes="1", rank=0))
        c.rendezvous()
        code = "import time; time.sleep(30)"
        # graft-lint: disable=R010 (jax-free sleeping children: the
        # watchdog kills one, the test kills the other; ~1s measured)
        stalled = subprocess.Popen([sys.executable, "-c", code])  # graft-lint: disable=R010
        quiet = subprocess.Popen([sys.executable, "-c", code])
        devnull = open(os.devnull, "ab")
        c.procs = [Proc(stalled, 0, os.devnull, devnull),
                   Proc(quiet, 1, os.devnull, devnull)]
        c._progress_seen = {}
        c.store.set("progress/0/0", b"7")   # rank 0 heartbeat, then frozen
        deadline = time.time() + 5
        while stalled.poll() is None and time.time() < deadline:
            c._check_stalls(0)
            time.sleep(0.05)
        assert stalled.poll() is not None, "stalled worker never killed"
        assert quiet.poll() is None, "uninstrumented worker was killed"
    finally:
        flags.set_flags({"elastic_stall_timeout_s": 0.0})
        for p in (stalled, quiet):
            if p is not None and p.poll() is None:
                p.kill()


def test_progress_reporter_publish_and_chaos_delay():
    """ProgressReporter publishes a monotonic heartbeat under the
    launcher's key scheme; the ``elastic.step`` delay site freezes it
    in place (the deterministic wedged-collective injection)."""
    from paddle_tpu.distributed.fleet.elastic import (ElasticContext,
                                                      ProgressReporter)
    from paddle_tpu.testing import chaos
    s = TCPStore(is_master=True)
    ctx = ElasticContext(generation=0, rank=0, world_size=1,
                         local_rank=0, nnodes=1,
                         master=f"127.0.0.1:{s.port}")
    rep = ProgressReporter(ctx=ctx)
    rep.publish(3)
    assert s.get("progress/0/0", timeout=5.0) == b"3"
    t0 = time.time()
    with chaos.delay_at("elastic.step", 0.3):
        rep.publish(4)
    assert time.time() - t0 >= 0.3
    assert s.get("progress/0/0", timeout=5.0) == b"4"


def test_late_joiner_requests_scale_up_restart():
    """A node that joins AFTER the world settled (its drawn rank falls
    beyond the settled count) must not run as an unwatched extra node:
    it announces a scale-up restart and both nodes re-rendezvous into
    a larger world one generation later."""
    from paddle_tpu.distributed.launch.main import CollectiveController

    c0 = CollectiveController(_ctrl_args(nnodes="1:2", rank=0,
                                         elastic_timeout=0.4))
    c0.rendezvous()             # alone: settles at 1 immediately
    assert c0.nnodes == 1
    done = []
    c1 = CollectiveController(_ctrl_args(nnodes="1:2", rank=-1,
                                         master=c0.master,
                                         elastic_timeout=0.4))
    t1 = threading.Thread(target=lambda: (c1.rendezvous(),
                                          done.append(1)))
    t1.start()
    # the late joiner announces the scale-up...
    deadline = time.time() + 10
    while time.time() < deadline:
        if c0.store.check("restart_generation") and \
                int(c0.store.get("restart_generation", timeout=5.0)) >= 1:
            break
        time.sleep(0.02)
    assert c0._peer_generation() >= 1, "late joiner never announced"
    # ...and the survivor adopts it (watch() would return PEER_RESTART)
    c0.restarts = c0._peer_generation()
    t0 = threading.Thread(target=lambda: (c0.rendezvous(),
                                          done.append(0)))
    t0.start()
    t0.join(20)
    t1.join(20)
    assert sorted(done) == [0, 1]
    assert c0.nnodes == 2 and c1.nnodes == 2
    assert c0.restarts == 1 and c1.restarts == 1
    assert {c0.node_rank, c1.node_rank} == {0, 1}


def test_elastic_death_watch_regeneration_rejoin():
    """Manager-level elastic lifecycle: node 1 dies -> m0's watch fires on
    the dead set -> next_generation() -> survivor re-registers and a
    replacement join()s -> collect_endpoints returns the rewritten roster."""
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    s = TCPStore(port=0, is_master=True, world_size=1)
    try:
        m0 = ElasticManager(s, node_id=0, nnodes=2, interval=0.1)
        m1 = ElasticManager(TCPStore(port=s.port), node_id=1, nnodes=2,
                            interval=0.1)
        m0.start()
        m1.start()
        m0.register("10.0.0.1:8000")
        m1.register("10.0.0.2:8000")
        assert m0.collect_endpoints(timeout=5) == ["10.0.0.1:8000",
                                                   "10.0.0.2:8000"]
        fired = threading.Event()
        seen = {}

        def on_change(dead, eps):
            seen["dead"] = dead
            fired.set()

        stop = m0.watch(on_change, poll=0.05)
        time.sleep(0.15)          # watcher baseline
        m1.stop()                 # the kill
        assert fired.wait(timeout=5), "watch never fired on node death"
        stop.set()
        assert 1 in seen["dead"]
        # re-rendezvous under the next generation: survivor re-registers,
        # a fresh replacement node joins the new namespace
        gen = m0.next_generation()
        assert gen == 1
        m0.register("10.0.0.1:8000")
        repl = ElasticManager(TCPStore(port=s.port), node_id=-1, nnodes=1,
                              generation=gen, interval=0.1)
        new_id = repl.join("10.0.0.9:8000")
        assert new_id == 1
        assert m0.collect_endpoints(timeout=5) == ["10.0.0.1:8000",
                                                   "10.0.0.9:8000"]
        m0.stop()
    finally:
        s.stop() if hasattr(s, "stop") else None

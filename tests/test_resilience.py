"""Resilience subsystem (resilience/): deterministic fault injection,
crash-resume, graceful degradation, checkpoint retention, and server
hardening.  The process-death faults run SOFT here (FaultInjected raise
instead of os._exit — same file state, survivable by pytest); the real
hard-crash path is exercised end-to-end by ``scripts/chaos_check.py``."""

import dataclasses
import json
import os
import socket
import sys
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.engine import checkpoint as ckpt_mod
from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.engine.spillpool import SpillPool
from raft_tla_tpu.models.dims import LEADER, RaftDims
from raft_tla_tpu.models.invariants import Bounds, build_constraint
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.resilience import faults
from raft_tla_tpu.resilience.faults import (FaultInjected, FaultPlan,
                                            SimulatedResourceExhausted,
                                            is_resource_exhausted)
from raft_tla_tpu.resilience.supervisor import (run_supervised,
                                                strip_supervisor_flags)

DIMS = RaftDims(n_servers=2, n_values=1, max_log=2, n_msg_slots=8)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faults.clear()


def make_engine(**kw):
    cfg = dict(batch=128, queue_capacity=1 << 12, seen_capacity=1 << 15,
               check_deadlock=False)
    cfg.update(kw)
    return BFSEngine(
        DIMS, invariants={"NoLeader": lambda st: jnp.all(st.role != LEADER)},
        constraint=build_constraint(DIMS, BOUNDS),
        config=EngineConfig(**cfg))


@pytest.fixture(scope="module")
def full_run():
    eng = make_engine()
    res = eng.run([init_state(DIMS)])
    assert res.stop_reason == "violation"
    return res


def read_events(path):
    with open(path, encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


# -- fault plan parsing / firing ----------------------------------------
def test_fault_plan_grammar():
    plan = FaultPlan.parse("ckpt_torn_write@level=3,kill@level=5,oom@grow=1",
                           hard=False)
    assert [f.site for f in plan.faults] == \
        ["ckpt_torn_write", "kill", "oom"]
    assert plan.faults[0].params == {"level": 3}     # int-typed
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultPlan.parse("explode@level=1")
    with pytest.raises(ValueError, match="key=value"):
        FaultPlan.parse("kill@level")
    with pytest.raises(ValueError, match="empty fault plan"):
        FaultPlan.parse(" , ")


def test_fault_fires_once_and_markers_persist(tmp_path):
    sd = str(tmp_path / "markers")
    plan = FaultPlan.parse("oom@grow=1", state_dir=sd, hard=False)
    with pytest.raises(SimulatedResourceExhausted) as ei:
        plan.fire("oom", grow=1)
    assert is_resource_exhausted(ei.value)
    assert plan.fire("oom", grow=1) is False         # fired already
    # A NEW plan instance (a restarted process) sees the same marker.
    plan2 = FaultPlan.parse("oom@grow=1", state_dir=sd, hard=False)
    assert plan2.fire("oom", grow=1) is False


def test_action_params_do_not_gate_matching():
    """``seconds`` configures trace_piece_delay's ACTION; no call site
    passes it as context, so matching must ignore it or the documented
    plan grammar can never fire."""
    plan = FaultPlan.parse("trace_piece_delay@seconds=0", hard=False)
    assert plan.fire("trace_piece_delay", piece=0) is True
    assert plan.fire("trace_piece_delay", piece=0) is False   # once


def test_ckpt_piece_missing_skips_the_write(tmp_path):
    ckdir = str(tmp_path / "states")
    make_engine(checkpoint_dir=ckdir, max_diameter=1).run(
        [init_state(DIMS)])
    ck = ckpt_mod.load(ckpt_mod.latest(ckdir))
    faults.install("ckpt_piece_missing@level=5;piece=1", hard=False)
    ckpt_mod.save(ckpt_mod.piece_path(ckdir, 5, 0, 2), ck)    # p0 lands
    ckpt_mod.save(ckpt_mod.piece_path(ckdir, 5, 1, 2), ck)    # p1 skipped
    assert os.path.exists(ckpt_mod.piece_path(ckdir, 5, 0, 2))
    assert not os.path.exists(ckpt_mod.piece_path(ckdir, 5, 1, 2))
    # The incomplete group must not be offered for resume.
    assert ckpt_mod.latest(ckdir).endswith("level_00001.npz")


def test_fault_param_mismatch_does_not_fire():
    plan = FaultPlan.parse("kill@level=5", hard=False)
    assert plan.fire("kill", level=4, chunk=1) is False
    assert plan.fire("oom", level=5) is False        # different site
    with pytest.raises(FaultInjected):
        plan.fire("kill", level=5, chunk=1)


# -- torn checkpoint write ----------------------------------------------
def test_torn_write_leaves_latest_on_previous_snapshot(tmp_path):
    ckdir = str(tmp_path / "states")
    faults.install("ckpt_torn_write@level=2", hard=False)
    eng = make_engine(checkpoint_dir=ckdir)
    with pytest.raises(FaultInjected):
        eng.run([init_state(DIMS)])
    # The crash window left the complete tmp behind, never renamed...
    assert os.path.exists(os.path.join(ckdir, "level_00002.npz.tmp"))
    assert not os.path.exists(os.path.join(ckdir, "level_00002.npz"))
    # ...and auto-resume falls back to the previous good snapshot.
    path = ckpt_mod.latest(ckdir)
    assert path is not None and path.endswith("level_00001.npz")
    ckpt_mod.load(path)                              # intact


def test_torn_write_then_resume_matches_full_run(full_run, tmp_path):
    ckdir = str(tmp_path / "states")
    faults.install("ckpt_torn_write@level=2", hard=False)
    with pytest.raises(FaultInjected):
        make_engine(checkpoint_dir=ckdir).run([init_state(DIMS)])
    faults.clear()
    r2 = make_engine().run(resume=ckpt_mod.latest(ckdir))
    assert r2.stop_reason == "violation"
    assert (r2.distinct, r2.generated, r2.diameter, r2.levels) == \
        (full_run.distinct, full_run.generated, full_run.diameter,
         full_run.levels)
    assert r2.violation.fingerprint == full_run.violation.fingerprint


# -- mid-level kill + resume --------------------------------------------
def test_mid_level_kill_resume_matches_full_run(full_run, tmp_path):
    ckdir = str(tmp_path / "states")
    faults.install("kill@level=2", hard=False)
    eng1 = make_engine(checkpoint_dir=ckdir)
    with pytest.raises(FaultInjected):
        eng1.run([init_state(DIMS)])
    faults.clear()
    path = ckpt_mod.latest(ckdir)
    assert path.endswith("level_00002.npz")   # died PAST the snapshot
    eng2 = make_engine()
    r2 = eng2.run(resume=path)
    assert (r2.distinct, r2.generated, r2.diameter, r2.levels) == \
        (full_run.distinct, full_run.generated, full_run.diameter,
         full_run.levels)
    # Counterexample replay works across the crash-resume boundary.
    steps = eng2.replay(r2.violation.fingerprint)
    assert steps[0][0] == -1
    assert steps[-1][1] == r2.violation.state


# -- graceful degradation (simulated RESOURCE_EXHAUSTED) -----------------
def test_oom_degrades_batch_and_completes(full_run, tmp_path):
    ckdir = str(tmp_path / "states")
    ev = str(tmp_path / "events.jsonl")
    faults.install("oom@level=2", hard=False)
    eng = make_engine(checkpoint_dir=ckdir, events_out=ev)
    res = eng.run([init_state(DIMS)])
    # Slow-but-correct: the run COMPLETED, at half the batch.
    assert res.stop_reason == "violation"
    assert eng.config.batch == 64
    assert (res.distinct, res.generated, res.diameter, res.levels) == \
        (full_run.distinct, full_run.generated, full_run.diameter,
         full_run.levels)
    degraded = [e for e in read_events(ev) if e["event"] == "degraded"]
    assert degraded and degraded[0]["new_batch"] == 64
    assert degraded[0]["resume_from"].endswith("level_00002.npz")
    assert eng.metrics.counter_value("engine/degraded") == 1


def test_oom_without_checkpoint_dir_restarts_from_scratch(full_run):
    faults.install("oom@level=1", hard=False)
    eng = make_engine()                     # no checkpoint_dir at all
    res = eng.run([init_state(DIMS)])
    assert res.stop_reason == "violation"
    assert res.distinct == full_run.distinct
    assert eng.config.batch == 64


def test_oom_respects_min_batch_floor():
    faults.install("oom@level=1", hard=False)
    eng = make_engine(batch=128, min_batch=128)   # halving would go under
    with pytest.raises(SimulatedResourceExhausted):
        eng.run([init_state(DIMS)])


def test_no_degrade_flag_fails_fast():
    faults.install("oom@level=1", hard=False)
    eng = make_engine(degrade_on_oom=False)
    with pytest.raises(SimulatedResourceExhausted):
        eng.run([init_state(DIMS)])


def test_grow_oom_retries_after_releasing_old_table():
    from raft_tla_tpu.ops import fpset
    eng = make_engine()
    n = 700                                  # past half of a 1024 table
    hi = np.arange(1, n + 1, dtype=np.uint32)
    lo = np.arange(1, n + 1, dtype=np.uint32)
    seen = fpset.from_host_keys(hi, lo, 1 << 10)[0]
    faults.install("oom@grow=1", hard=False)
    grown = eng._maybe_grow_seen(seen)
    assert grown.hi.shape[0] == 1 << 11      # doubled despite the OOM
    assert int(grown.size) == n
    assert eng.metrics.counter_value("engine/degraded") == 1


# -- checkpoint retention GC --------------------------------------------
def test_keep_checkpoints_bounds_the_dir(tmp_path):
    ckdir = str(tmp_path / "states")
    eng = make_engine(checkpoint_dir=ckdir, keep_checkpoints=2,
                      max_diameter=4)
    eng.run([init_state(DIMS)])
    snaps = sorted(n for n in os.listdir(ckdir) if n.endswith(".npz"))
    assert snaps == ["level_00003.npz", "level_00004.npz"]
    assert ckpt_mod.latest(ckdir).endswith("level_00004.npz")


def test_gc_never_counts_garbage_toward_keep(tmp_path):
    ckdir = str(tmp_path / "states")
    make_engine(checkpoint_dir=ckdir, max_diameter=2).run(
        [init_state(DIMS)])
    # Two torn higher-level files must not evict the good snapshots.
    for lvl in (7, 8):
        with open(os.path.join(ckdir, f"level_{lvl:05d}.npz"), "wb") as f:
            f.write(b"\x00garbage")
    removed = ckpt_mod.gc(ckdir, keep=2)
    assert ckpt_mod.latest(ckdir).endswith("level_00002.npz")
    assert os.path.exists(os.path.join(ckdir, "level_00001.npz"))
    assert removed >= 1                      # level_00000 went


def test_gc_negative_keep_means_keep_all(tmp_path):
    ckdir = str(tmp_path / "states")
    make_engine(checkpoint_dir=ckdir, max_diameter=2).run(
        [init_state(DIMS)])
    before = sorted(os.listdir(ckdir))
    assert ckpt_mod.gc(ckdir, keep=-1) == 0  # never "delete everything"
    assert ckpt_mod.gc(ckdir, keep=None) == 0
    assert sorted(os.listdir(ckdir)) == before


def test_gc_collects_old_torn_tmp_debris(tmp_path):
    """Crash debris below the retention cutoff — orphaned .tmp files,
    incomplete piece groups — must be collected too, or a long
    supervised run with repeated crashes grows the dir without bound."""
    ckdir = str(tmp_path / "states")
    make_engine(checkpoint_dir=ckdir, max_diameter=3).run(
        [init_state(DIMS)])
    with open(os.path.join(ckdir, "level_00001.npz.tmp"), "wb") as f:
        f.write(b"torn")                     # a torn write's leftover
    with open(os.path.join(ckdir, "level_00000.p0of2.npz"), "wb") as f:
        f.write(b"lonely piece")             # incomplete old group
    ckpt_mod.gc(ckdir, keep=2)               # keeps levels 3 and 2
    left = sorted(n for n in os.listdir(ckdir) if n.startswith("level_"))
    assert left == ["level_00002.npz", "level_00003.npz"]


# -- mixed-generation piece groups --------------------------------------
def test_latest_skips_mixed_generation_piece_group(tmp_path):
    ckdir = str(tmp_path / "states")
    make_engine(checkpoint_dir=ckdir, max_diameter=1).run(
        [init_state(DIMS)])
    good = ckpt_mod.latest(ckdir)
    assert good.endswith("level_00001.npz")
    ck = ckpt_mod.load(good)
    # A level-5 piece group whose halves disagree on counters — the
    # footprint of a crash BETWEEN piece overwrites.
    ckpt_mod.save(ckpt_mod.piece_path(ckdir, 5, 0, 2), ck)
    ckpt_mod.save(ckpt_mod.piece_path(ckdir, 5, 1, 2),
                  dataclasses.replace(ck, distinct=ck.distinct + 1))
    # load() on the group still raises (the guard this fallback covers)…
    with pytest.raises(ValueError, match="generations"):
        ckpt_mod.load(ckpt_mod.piece_path(ckdir, 5, 0, 2))
    # …but latest() now SKIPS it instead of handing resume a dead path.
    assert ckpt_mod.latest(ckdir) == good


# -- spill write retry ---------------------------------------------------
def test_spill_write_failure_retries_once(tmp_path):
    faults.install("spill_write@attempt=1", hard=False)
    pool = SpillPool(str(tmp_path / "spill"))
    rows = np.arange(64, dtype=np.uint8).reshape(8, 8)
    pool.append(rows)                        # first attempt fails inside
    assert pool.total_rows() == 8
    np.testing.assert_array_equal(np.asarray(pool.pop(0)), rows)


def test_spill_write_two_failures_surface(tmp_path):
    faults.install("spill_write@attempt=1,spill_write@attempt=2",
                   hard=False)
    pool = SpillPool(str(tmp_path / "spill"))
    with pytest.raises(OSError, match="twice"):
        pool.append(np.zeros((4, 4), np.uint8))
    assert pool.total_rows() == 0            # no torn segment queued


# -- supervisor ----------------------------------------------------------
def test_supervisor_restarts_crashing_child(tmp_path):
    marker = str(tmp_path / "crashed_once")
    ev = str(tmp_path / "events.jsonl")
    script = (
        "import os, sys\n"
        f"m = {marker!r}\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').close(); sys.exit(86)\n"
        "sys.exit(0)\n")
    rc = run_supervised([sys.executable, "-c", script],
                        checkpoint_dir=str(tmp_path / "states"),
                        events_out=ev, max_restarts=3,
                        backoff_seconds=0.01)
    assert rc == 0
    events = read_events(ev)
    restarts = [e for e in events if e["event"] == "restart"]
    assert len(restarts) == 1
    assert restarts[0]["exit_code"] == 86
    assert restarts[0]["attempt"] == 1
    assert [e for e in events if e["event"] == "supervised_done"]


def test_supervisor_gives_up_after_budget(tmp_path):
    ev = str(tmp_path / "events.jsonl")
    rc = run_supervised([sys.executable, "-c", "import sys; sys.exit(9)"],
                        checkpoint_dir=str(tmp_path / "states"),
                        events_out=ev, max_restarts=2,
                        backoff_seconds=0.01)
    assert rc == 9
    events = read_events(ev)
    assert len([e for e in events if e["event"] == "restart"]) == 2
    assert [e for e in events if e["event"] == "supervise_giveup"]


def _exit1_child(ev_path, stop_reason):
    """Fake child: append a run_end with ``stop_reason`` and exit 1 —
    the two faces of a 1-exit the supervisor must tell apart."""
    return (
        "import json, sys\n"
        f"open({ev_path!r}, 'a').write(json.dumps("
        f"{{'event': 'run_end', 'ts': 0, 'stop_reason': "
        f"{stop_reason!r}}}) + '\\n')\n"
        "sys.exit(1)\n")


def test_supervisor_treats_violation_exit_as_done(tmp_path):
    ev = str(tmp_path / "events.jsonl")
    rc = run_supervised([sys.executable, "-c", _exit1_child(ev, "violation")],
                        checkpoint_dir=str(tmp_path / "states"),
                        events_out=ev, max_restarts=3,
                        backoff_seconds=0.01)
    assert rc == 1                  # counterexample found == completed
    assert not [e for e in read_events(ev) if e["event"] == "restart"]


def test_supervisor_retries_exception_exit_1(tmp_path):
    """An uncaught Python exception ALSO exits 1 — without the run_end
    completion receipt it must be retried, not reported as a result."""
    ev = str(tmp_path / "events.jsonl")
    rc = run_supervised([sys.executable, "-c", _exit1_child(ev, "error")],
                        checkpoint_dir=str(tmp_path / "states"),
                        events_out=ev, max_restarts=2,
                        backoff_seconds=0.01)
    assert rc == 1
    assert len([e for e in read_events(ev)
                if e["event"] == "restart"]) == 2


def test_supervisor_honors_initial_resume_on_first_attempt(tmp_path):
    argv_log = str(tmp_path / "argvs")
    script = ("import sys\n"
              f"open({argv_log!r}, 'a').write("
              "' '.join(sys.argv[1:]) + '\\n')\n"
              "sys.exit(0)\n")
    rc = run_supervised([sys.executable, "-c", script],
                        checkpoint_dir=str(tmp_path / "states"),
                        events_out=str(tmp_path / "ev.jsonl"),
                        initial_resume="auto", backoff_seconds=0.01)
    assert rc == 0
    with open(argv_log) as f:
        assert f.read().splitlines() == ["--resume auto"]


def test_supervisor_restart_ignores_preexisting_stale_snapshot(tmp_path):
    """A reused states/ dir: the child crashed before writing ANY
    snapshot of its own, so the restart must run from scratch — not
    resume a previous run's stale image (load() validates only dims)."""
    ckdir = str(tmp_path / "states")
    make_engine(checkpoint_dir=ckdir, max_diameter=1).run(
        [init_state(DIMS)])                  # the "previous run's" image
    argv_log = str(tmp_path / "argvs")
    marker = str(tmp_path / "crashed_once")
    script = ("import os, sys\n"
              f"open({argv_log!r}, 'a').write("
              "' '.join(sys.argv[1:]) + '\\n')\n"
              f"m = {marker!r}\n"
              "if not os.path.exists(m):\n"
              "    open(m, 'w').close(); sys.exit(86)\n"
              "sys.exit(0)\n")
    rc = run_supervised([sys.executable, "-c", script],
                        checkpoint_dir=ckdir,
                        events_out=str(tmp_path / "ev.jsonl"),
                        max_restarts=2, backoff_seconds=0.01)
    assert rc == 0
    with open(argv_log) as f:
        assert f.read().splitlines() == ["", ""]   # no --resume either time


def test_supervisor_does_not_retry_usage_errors(tmp_path):
    ev = str(tmp_path / "events.jsonl")
    rc = run_supervised([sys.executable, "-c", "import sys; sys.exit(2)"],
                        checkpoint_dir=str(tmp_path / "states"),
                        events_out=ev, max_restarts=3,
                        backoff_seconds=0.01)
    assert rc == 2
    events = read_events(ev)
    assert not [e for e in events if e["event"] == "restart"]
    assert [e for e in events if e["event"] == "supervise_giveup"]


def test_supervise_parent_never_initialises_a_backend(tmp_path):
    """One process per chip: the ``--supervise`` parent only spawns the
    checking child, so it must never initialise a jax backend — a parent
    holding the chip would make every child fail or hang."""
    import os
    import subprocess
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = (
        "import sys\n"
        "import raft_tla_tpu.resilience.supervisor as sup\n"
        "sup.subprocess.call = lambda argv, env=None: 2\n"
        "from raft_tla_tpu.cli import main\n"
        f"rc = main(['check', {os.path.join(repo, 'configs/MCraft_noleader.cfg')!r},"
        f" '--supervise', '--checkpoint-dir', {str(tmp_path / 'ck')!r},"
        f" '--events-out', {str(tmp_path / 'ev.jsonl')!r}])\n"
        "xb = sys.modules.get('jax._src.xla_bridge')\n"
        "assert xb is None or not xb.backends_are_initialized()\n"
        "print('PARENT_RC', rc)\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=repo,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "PARENT_RC 2" in r.stdout


def test_strip_supervisor_flags():
    assert strip_supervisor_flags(
        ["check", "m.cfg", "--supervise", "5", "--batch", "64"]) == \
        ["check", "m.cfg", "--batch", "64"]
    assert strip_supervisor_flags(
        ["check", "m.cfg", "--supervise=5", "--resume", "auto"]) == \
        ["check", "m.cfg"]
    assert strip_supervisor_flags(
        ["check", "m.cfg", "--resume=auto", "--supervise"]) == \
        ["check", "m.cfg"]
    assert strip_supervisor_flags(
        ["check", "--supervise", "--no-trace", "m.cfg"]) == \
        ["check", "--no-trace", "m.cfg"]


# -- server hardening ----------------------------------------------------
@pytest.fixture()
def hardened_server():
    from raft_tla_tpu import server as srv_mod
    srv = srv_mod.serve(port=0, max_request_bytes=1024,
                        idle_timeout_seconds=1.0)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv.server_address
    srv.shutdown()


def test_server_rejects_oversized_request_line(hardened_server):
    with socket.create_connection(hardened_server, timeout=30) as s:
        s.sendall(b'{"op": "ping", "junk": "' + b"x" * 4096 + b'"}\n')
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
        resp = json.loads(buf)
        assert resp["ok"] is False
        assert "exceeds" in resp["error"]
        # The connection is CLOSED after the reject (no resync possible).
        s.settimeout(10)
        assert s.recv(1) == b""


def test_server_drops_idle_connection(hardened_server):
    with socket.create_connection(hardened_server, timeout=30) as s:
        # A live request first: the timeout is per-read, not per-conn.
        s.sendall(b'{"op": "ping"}\n')
        buf = b""
        while not buf.endswith(b"\n"):
            buf += s.recv(65536)
        assert json.loads(buf)["ok"] is True
        time.sleep(1.5)                      # past the 1 s idle timeout
        # Silent close — no unsolicited error line that a pooled client
        # could misread as the response to its NEXT request.
        s.settimeout(10)
        assert s.recv(65536) == b""

"""The swarm tier as a deployment (``configs/MCraft_swarm.cfg``, benchmark
configuration ``mcraft3-swarm``): one way to build its engine, the walks it
reports held to the plain reference's rules, its counters, and that naming
the walk chunk's stages changed no bit.

CPU, small W.  The reference is ``benchmark/reference`` (``walk.py``,
``oracle.py``), which imports nothing of the program.
"""

import json
import math
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import bench_lib as lib  # noqa: E402
from raft_tla_tpu.engine.check import (initial_states,  # noqa: E402
                                       make_swarm_engine, resolve_constraint,
                                       resolve_invariants)
from raft_tla_tpu.engine.swarm import (SWARM_COUNTERS,  # noqa: E402
                                       WALK_STAGES, SwarmEngine)
from raft_tla_tpu.models.dims import LEADER  # noqa: E402
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402

CFG = os.path.join(REPO, "configs", "MCraft_swarm.cfg")
DEPTH = 100


@pytest.fixture(scope="module")
def setup():
    return load_config(CFG)


@pytest.fixture(scope="module")
def ref():
    r = lib.reference(lib.load_json("configs", "mcraft3-swarm.json"))
    from reference import walk
    r.walk = walk
    return r


@pytest.fixture(scope="module")
def hunted(setup, tmp_path_factory):
    """One finished hunt of 192 walkers in slices of 64, its event log."""
    log = str(tmp_path_factory.mktemp("hunt") / "events.jsonl")
    eng = make_swarm_engine(setup, walks=192, batch=64, max_depth=DEPTH,
                            events_out=log)
    res = eng.run(initial_states(setup), seed=3)
    return eng, res, lib.read_events(log)


def reference_states(steps, ref):
    return [lib.to_reference_state(s, ref.pystate) for _a, s in steps]


def families(eng, actions):
    return [eng.dims.instance_info(g)[0] for g in actions]


# -- one normal path ---------------------------------------------------------

def test_the_cfg_is_the_canarys_spec_letter_for_letter(setup):
    """Constants, invariant, constraint and deadlock setting of
    MCraft_noleader.cfg; only the TPU directives differ."""
    def spec(path):
        with open(path, encoding="utf-8") as f:
            return [ln.rstrip() for ln in f
                    if ln.strip() and not ln.startswith("\\*")]
    assert spec(CFG) == spec(os.path.join(REPO, "configs",
                                          "MCraft_noleader.cfg"))
    assert setup.backend["MODE"] == "swarm"
    config = lib.load_json("configs", "mcraft3-swarm.json")
    assert (setup.backend["WALKS"], setup.backend["BATCH"]) == (
        config["walks"], config["batch"])
    with open(CFG, encoding="utf-8") as f:
        assert f.read().rstrip("\n").split("\n") == config["cfg_text"]


def shape(eng):
    return (type(eng).__name__, eng.walks, eng.max_depth, eng.batch,
            eng.chunk, eng.ring, eng.pipeline_name, eng.hunt)


def test_cli_server_and_bench_build_the_same_engine(tmp_path, monkeypatch,
                                                    capsys):
    """``check <cfg>``, the server's swarm branch and ``bench.py``'s
    BENCH_MODE=swarm all go through ``make_swarm_engine`` and, for the
    same cfg with nothing else said, get the same engine."""
    import bench
    from raft_tla_tpu import cli, server
    from raft_tla_tpu.engine import check
    import re
    with open(CFG, encoding="utf-8") as f:
        text = re.sub(r"BATCH = \d+", "BATCH = 32",
                      re.sub(r"WALKS = \d+", "WALKS = 96", f.read()))
    cfg = tmp_path / "MCraft_swarm.cfg"
    cfg.write_text(text)
    built = []
    real = check.make_swarm_engine

    def recording(setup, **kw):
        eng = real(setup, **kw)
        built.append(shape(eng))
        return eng

    monkeypatch.setattr(check, "make_swarm_engine", recording)
    assert cli.main(["check", str(cfg)]) == 1           # the violation
    server._do_swarm({"cfg_text": text})
    for name in ("BENCH_WALKS", "BENCH_MAX_DEPTH", "BENCH_BATCH",
                 "BENCH_RING", "BENCH_CHUNK", "BENCH_PIPELINE"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setenv("BENCH_NUM_STEPS", "32")
    monkeypatch.setenv("BENCH_EVENTS_OUT", str(tmp_path / "bench.jsonl"))
    bench._swarm_bench(load_config(str(cfg)), "cpu")
    capsys.readouterr()
    assert len(built) == 3 and len(set(built)) == 1, built
    assert built[0] == ("SwarmEngine", 96, 128, 32, 32, 16, "v2", True)


def test_overrides_and_the_computed_slice_width(setup):
    from raft_tla_tpu.engine.check import swarm_slice_width
    eng = make_swarm_engine(setup, walks=40, max_depth=DEPTH, batch=16)
    assert shape(eng)[1:4] == (40, DEPTH, 16)
    # Neither the caller nor the cfg gives a width: one slice while the
    # hunt's lanes x lanes prior is small, never more lanes than walks.
    assert swarm_slice_width(48) == 48
    assert swarm_slice_width(1 << 20) <= 1 << 15
    assert swarm_slice_width(1 << 20, hunt=False) == 1 << 20


# -- the walks it reports, against the reference's rules -----------------------

def test_the_reported_violation_keeps_the_references_rules(hunted, ref):
    eng, res, _events = hunted
    steps = eng.replay(res.violation.fingerprint)
    states = reference_states(steps, ref)
    acts = [g for g, _s in steps[1:]]
    assert ref.walk.check_transcript(
        states[0], families(eng, acts), states[1:], dims=ref.dims,
        depth=DEPTH, constraint=ref.constraint, whole=False) == []
    assert not ref.rd.no_leader_py(states[-1], ref.dims)
    assert all(ref.rd.no_leader_py(s, ref.dims) for s in states[:-1])
    assert 9 <= len(states) - 1 <= DEPTH


def test_walk_transcripts_replay_to_the_devices_rows(hunted, ref):
    """Every walker of the finished hunt: its current trace keeps the
    rules, and replays to the row the device holds."""
    from raft_tla_tpu.models.schema import decode_state, unflatten_state
    eng, res, _events = hunted
    ids = list(range(eng.walks))
    lengths = []
    for w, (root, actions, row) in zip(ids, eng.walk_transcripts(ids)):
        steps = eng.replay_actions(root, actions)
        assert len(steps) == len(actions) + 1, w
        states = reference_states(steps, ref)
        assert ref.walk.check_transcript(
            states[0], families(eng, actions), states[1:], dims=ref.dims,
            depth=DEPTH, constraint=ref.constraint) == [], w
        held = decode_state(unflatten_state(row, eng.dims), eng.dims)
        assert lib.to_reference_state(held, ref.pystate) == states[-1], w
        lengths.append(len(actions))
    assert max(lengths) >= 3        # not only walkers that just restarted


def test_check_transcript_sees_a_broken_walk(hunted, ref):
    eng, res, _events = hunted
    steps = eng.replay(res.violation.fingerprint)
    states = reference_states(steps, ref)
    fams = families(eng, [g for g, _s in steps[1:]])
    rules = dict(dims=ref.dims, depth=DEPTH, constraint=ref.constraint)
    # a step left out, a wrong family, a revisit, a state past the bounds
    assert ref.walk.check_transcript(states[0], fams[1:], states[2:],
                                     **rules)
    assert ref.walk.check_transcript(
        states[0], [(f + 1) % 10 for f in fams], states[1:], whole=False,
        **rules)
    assert ref.walk.check_transcript(
        states[0], fams[:2] + fams[1:2], states[1:3] + states[2:3],
        **rules)
    assert ref.walk.check_transcript(states[0], fams[:3], states[1:4],
                                     depth=3, dims=ref.dims)
    assert ref.walk.check_transcript(
        states[0], fams[:4], states[1:5], dims=ref.dims, depth=DEPTH,
        constraint=lambda s, d: s != states[3])


def test_leader_share_agrees_with_the_reference_walker(setup, ref):
    """Traces that reach a leader within 100 steps, as a share of the
    traces walked: the program's and the reference walker's.

    Both walk with "no leader" made part of the constraint, so a trace
    that reaches a leader ends there: the program counts them as chosen
    BecomeLeader steps (every one leads to a leader), the reference as
    traces ended by its invariant.  The two are independent binomial
    samples of one p if the rules are the same (the draw among enabled
    instances of a random half of the families, the ring, the restarts);
    the bound is 4 standard deviations of the difference of their
    shares, sqrt(p (1 - p) (1/n1 + 1/n2)) at the pooled p: about 5.5e-4
    on p = 1.25e-3 here, which a walker without the family subset (some
    20 times fewer leaders, engine/swarm.py) or with a wrong ring
    misses by far.  The share of traces ended by the constraint (0.45,
    same bound: 0.0075) is held too: it moves with every rule.  The
    program's walkers still under way when its step budget ends are
    256 of 170,000 traces: no bias to speak of."""
    base = resolve_constraint(setup)
    eng = SwarmEngine(
        setup.dims, invariants={},
        constraint=lambda st: base(st) & jnp.all(st.role != LEADER),
        walks=256, max_depth=DEPTH, batch=256)
    res = eng.run(initial_states(setup), seed=11, num_steps=2048)
    hunt = res.report["hunt"]
    n1 = hunt["restarts"]["total"]
    lead1 = next(f["chosen"] for f in hunt["families"]
                 if f["family"] == "BecomeLeader")
    cons1 = hunt["restarts"]["constraint"] - lead1
    n2 = 120_000
    got = ref.walk.census(ref.dims, 7, n2, depth=DEPTH,
                          constraint=ref.constraint,
                          invariant=ref.rd.no_leader_py)
    assert n1 > 150_000 and lead1 > 100 and got["violation"] > 75

    def four_sigma(k1, k2):
        p = (k1 + k2) / (n1 + n2)
        return 4 * math.sqrt(p * (1 - p) * (1 / n1 + 1 / n2))
    assert abs(lead1 / n1 - got["violation"] / n2) <= four_sigma(
        lead1, got["violation"]), (lead1, n1, got)
    assert abs(cons1 / n1 - got["constraint"] / n2) <= four_sigma(
        cons1, got["constraint"]), (cons1, n1, got)


# -- counters, spans, stages -----------------------------------------------------

def test_run_end_carries_the_counters_and_they_conserve(hunted):
    eng, res, events = hunted
    end = next(e for e in events if e["event"] == "run_end")
    assert all(k in end for k in SWARM_COUNTERS) and "compiles" in end
    slices = -(-eng.walks // eng.batch)
    rounds = end["chunk_calls"] // slices
    assert end["slices"] == slices and end["chunk_calls"] == slices * rounds
    assert end["steps"] == eng.walks * eng.chunk * rounds == res.steps
    assert end["swarm"]["traces"] == eng.walks + end["restarts"]
    hunt = next(e for e in events if e["event"] == "hunt")["hunt"]
    assert hunt["restarts"]["total"] == end["restarts"]
    assert end["latch_step"] == res.violation_step
    assert end["steps_past_latch"] == eng.walks * (
        rounds * eng.chunk - end["latch_step"])
    # 17 copies a slice a round, two more for each slice that latched
    assert end["fetches"] >= 17 * end["chunk_calls"] + 2
    # the latched trace rebuilt in one call of the fused program
    # (engine/replay.py), no expand round trip a step
    assert (end["reconstruct_scans"], end["reconstruct_steps"]) == (1, 0)
    assert eng.metrics.counter_value("engine/replay_scan_steps") >= len(
        eng.replay(res.violation.fingerprint)) - 1
    # the registry's sums, for a process that serves many runs
    assert eng.metrics.counter_value("swarm/chunk_calls") \
        == end["chunk_calls"]
    assert eng.metrics.gauge_value("swarm/latch_step") == end["latch_step"]


def test_the_spans_of_a_run(hunted):
    eng, _res, _events = hunted
    hist = eng.metrics.snapshot()["histograms"]
    for name in ("scope/run", "scope/reconstruct", "phase/swarm_init",
                 "phase/swarm_chunk", "phase/swarm_fetch",
                 "phase/replay_scan", "phase/run_end"):
        assert hist[name]["count"] >= 1, name
    assert hist["phase/replay_scan"]["count"] \
        == eng.metrics.counter_value("engine/replay_scans")
    assert "phase/reconstruct_step" not in hist


def test_the_walk_chunk_names_its_stages(setup):
    eng = make_swarm_engine(setup, walks=8, batch=8, max_depth=DEPTH)
    text = eng._chunk.lower(*eng.chunk_avals(1)).as_text(debug_info=True)
    import re
    for stage in WALK_STAGES:       # a whole component of a scope path
        assert re.search(r'loc\("(?:[^"]*/)?%s[/"]' % stage, text), stage
    assert 'walk_stages_tag = "w1"' in text


# What commit 6b857bc (the parent of the PR that named the stages and
# added the counters) gives for these runs, computed there: 48 walkers
# without an invariant for 24 steps (steps, visits, traces, sha256 of the
# sorted visited fingerprints), and a hunt of 192 (steps, visits, traces,
# the violation's fingerprint, its trace's length, the deepest trace).
PARENT_WALK = (1152, 794, 406, "3813d18ca5e6ae25")
PARENT_HUNT = (6144, 4178, 2158, 0xd6467ee051491c1d, 10, 13)


@pytest.mark.parametrize("batch", [48, 16, 7])
def test_stages_and_counters_change_no_bit(setup, batch):
    import hashlib
    canary = load_config(os.path.join(REPO, "configs",
                                      "MCraft_noleader.cfg"))
    eng = SwarmEngine(canary.dims, invariants={},
                      constraint=resolve_constraint(canary), walks=48,
                      max_depth=12, batch=batch, chunk=8,
                      collect_fingerprints=True)
    res = eng.run(initial_states(canary), seed=5, num_steps=24)
    fps = res.visited_fingerprints
    fps = fps[np.lexsort((fps[:, 1], fps[:, 0]))]
    assert (res.steps, res.visited, res.traces,
            hashlib.sha256(fps.tobytes()).hexdigest()[:16]) == PARENT_WALK
    eng = SwarmEngine(canary.dims, invariants=resolve_invariants(canary),
                      constraint=resolve_constraint(canary), walks=192,
                      max_depth=DEPTH, batch=batch, chunk=32)
    res = eng.run(initial_states(canary), seed=3)
    assert (res.steps, res.visited, res.traces, res.violation.fingerprint,
            len(eng.replay(res.violation.fingerprint)),
            res.diameter) == PARENT_HUNT


# -- the benchmark's own record --------------------------------------------------

def test_the_pinned_hunts_are_the_programs_own_at_any_slicing(setup):
    """Two seeds of ``benchmark/pinned/mcraft3-swarm.jsonl`` made again
    here, in slices of 1,024 with the observatory on (the record was made
    at one slice with it off)."""
    config = lib.load_json("configs", "mcraft3-swarm.json")
    mix = lib.load_json("traffic", "hunts-noleader.json")
    with open(os.path.join(REPO, "benchmark", "pinned",
                           config["pinned_hunts"] + ".jsonl"),
              encoding="utf-8") as f:
        pinned = {r["seed"]: r for r in map(json.loads, f)}
    assert sorted(pinned) == mix["seeds"]
    assert all(r["walks"] == config["walks"]
               and 0 <= r["latch_step"] < config["chunk"]
               for r in pinned.values())
    eng = make_swarm_engine(setup, walks=min(config["walks"], 4096),
                            max_depth=config["max_depth"], batch=1024)
    # The first violation in (step, walk) order over all the walkers is
    # also the first over the walkers this test's smaller swarm has, where
    # it is one of them.
    seeds = [s for s in mix["seeds"] if pinned[s]["walk"] < eng.walks][:2]
    assert len(seeds) == 2
    for seed in seeds:
        want = pinned[seed]
        res = eng.run(initial_states(setup), seed=seed)
        assert (res.violation_step, res.violation_walk,
                f"{res.violation.fingerprint:#018x}",
                len(eng.replay(res.violation.fingerprint))) == (
            want["latch_step"], want["walk"], want["fingerprint"],
            want["trace_len"]), seed

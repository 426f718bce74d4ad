"""Static partial-order reduction (analysis/por.py + EngineConfig.por).

Three layers of evidence, mirroring the pass's own soundness gates:

- **Certificates**: on the base Raft alphabet the pass is honestly
  conservative — every instance fails the dependence-closure condition
  (``Receive``'s whole-bag reply-slot scan makes it statically dependent
  on everything), so the certified set is EMPTY, each family carries a
  surfaced WARNING naming the blocking condition, and POR-on checking is
  bit-identical to full expansion.  The pinned L0-L9 MCraft_bounded
  ground truths (scripts/oracle_exhaust.py) are re-checked POR-on.
- **Table integrity**: the packed reduction table is fingerprinted over
  its payload; a hand-edited mask, a different model, or a run checking
  predicates outside the certified set is rejected at admission.
- **Engine machinery**: a test-forged table (simulating a model where
  certificates prove) drives the masked expansion path end-to-end:
  generated/distinct drop, the reduced distinct-state set is a subset of
  the full run's (trace-fingerprint check), and the coverage accounting
  closes exactly (``expanded * family_size == generated + disabled +
  pruned`` per family).
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from raft_tla_tpu.analysis import por
from raft_tla_tpu.engine.bfs import BFSEngine, EngineConfig
from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.dims import LEADER, RaftDims
from raft_tla_tpu.models.invariants import (Bounds, build_constraint,
                                            build_type_ok, constraint_py,
                                            type_ok_py)
from raft_tla_tpu.models.pystate import init_state

DIMS = RaftDims(n_servers=3, n_values=2, max_log=4, n_msg_slots=8)
BOUNDS = Bounds(max_term=2, max_log_len=1, max_msg_count=1)


@pytest.fixture(scope="module", autouse=True)
def _release_tracing_caches():
    """Same contract as tests/test_analysis.py: the pass traces every
    kernel and predicate; drop the caches at module teardown so the
    accumulated trace churn never taxes other modules."""
    yield
    import gc

    import jax

    from raft_tla_tpu.analysis import interp
    interp.traced_kernels.cache_clear()
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="module")
def pass_result():
    from raft_tla_tpu.analysis import effects
    summary, _ = effects.analyze(DIMS)
    return por.analyze(DIMS, bounds=BOUNDS, effect_summary=summary)


@pytest.fixture(scope="module")
def real_table():
    """The genuinely-certified table for (DIMS, TypeOK, BoundedSpace):
    conservative — zero ample instances on the Raft alphabet."""
    return por.build_table(
        DIMS, invariants={"TypeOK": build_type_ok(DIMS)},
        constraint=build_constraint(DIMS, BOUNDS))


def small_config(**kw):
    base = dict(batch=32, queue_capacity=1 << 12, seen_capacity=1 << 15,
                check_deadlock=False, max_diameter=3)
    base.update(kw)
    return EngineConfig(**base)


def forged_dup_table(dims=DIMS, predicates=("TypeOK", "CONSTRAINT")):
    """A table certifying every DuplicateMessage instance — NOT a sound
    certificate for Raft (the pass proves it cannot be); it exists to
    drive the engine's masking machinery in tests, standing in for a
    model whose certificates do prove."""
    G = dims.n_instances
    mask = np.zeros(G, bool)
    f = dims.family_names.index("DuplicateMessage")
    off, sz = dims.family_offsets[f], dims.family_sizes[f]
    mask[off:off + sz] = True
    return por.PorTable(model=repr(dims), n_instances=G, ample_mask=mask,
                        priority=np.arange(G, dtype=np.int32),
                        predicates=tuple(predicates))


# ---------------------------------------------------------------------------
# The pass: conservative certificates on the real model


def test_pass_is_clean_and_honestly_conservative(pass_result):
    summary, findings = pass_result
    assert [f for f in findings if f.severity == "ERROR"] == []
    # Honest negative result: nothing certifies on the Raft alphabet.
    assert summary["certified"] == 0
    widened = {f.field for f in findings if f.code == "por-widened"}
    assert widened == set(DIMS.family_names)
    # Every family's blocking conditions are recorded; closure is the
    # universal blocker (Receive genuinely addresses any server and its
    # reply allocation scans the whole bag), and each family carries
    # its top blocking (family, field, slot) triples as the worklist.
    for fam, d in summary["families"].items():
        assert d["certified"] == 0
        assert d["blocked_by"].get("closure", 0) == d["instances"], fam
        top = d["blocking_elements"]
        assert top and {"family", "element", "kind", "pairs"} \
            <= set(top[0]), fam


def test_closure_block_is_machine_checked_impossible(pass_result):
    """The impossibility notes: every instance blocked on closure has a
    CONCRETE two-action non-commutation witness (or an interval proof
    it can never execute) — so the zero-certified result is inherent to
    the Raft alphabet, pinned, and can never be mistaken for analyzer
    imprecision."""
    summary, findings = pass_result
    ref = summary["closure_refutation"]
    assert ref["ran"]
    assert ref["open"] == []
    assert ref["witnessed"] + ref["vacuous"] == summary["n_instances"]
    imposs = {f.field for f in findings if f.code == "por-impossible"}
    assert imposs == set(DIMS.family_names)
    # The witness detail names the conflicting instance and the kind.
    fam = summary["families"]["DuplicateMessage"]
    w = fam["closure_refutation"]["witnesses"][0]
    assert w["status"] == "witnessed"
    assert w["kind"] in ("disables", "disabled-by", "diamond")
    assert w["conflicts_with"]
    # The vacuous instances are exactly the never-enabled grid corners
    # (AppendEntries(i, i) — guard has i != j parameter-concrete).
    ae = summary["families"]["AppendEntries"]["closure_refutation"]
    assert ae["vacuous"] == DIMS.n_servers


def test_receive_case_split_slot_local(pass_result):
    """The mtype/(i, j) case-split: each case's server-field writes are
    row-local to the case's dest server, the union over cases stays
    inside the instance's conservative footprint, and the por summary
    records it — the machine-readable reason the whole-field union is
    forced by reachable headers."""
    from raft_tla_tpu.analysis import effects
    summary, _ = pass_result
    cs = summary["families"]["Receive"]["case_split"]
    assert cs["cases"] == 4 * DIMS.n_servers * DIMS.n_servers
    assert cs["server_writes_row_local"] == cs["cases"]
    cases = effects.receive_case_effects(DIMS, slot=0)
    eff, _f = effects.analyze(DIMS)
    recv = next(i for i in eff.instances if i.label == "Receive(slot=0)")
    server_fields = {"term", "role", "voted_for", "votes_resp",
                     "votes_gran", "log_term", "log_val", "log_len",
                     "next_idx", "match_idx"}
    for (t, i, j), fp in cases.items():
        for f, m in fp["writes"].items():
            assert bool((m & ~recv.writes[f]).sum() == 0), (t, i, j, f)
            if f in server_fields:
                rows = set(np.nonzero(m)[0].tolist())
                assert rows <= {i}, (t, i, j, f, rows)
    # AER on a known (i, j): the handler's footprint is cell-local.
    aer = cases[(3, 1, 2)]["writes"]
    assert aer["next_idx"].tolist()[1][2] and aer["next_idx"].sum() == 1
    assert aer["msg_cnt"].tolist() == [1] + [0] * (DIMS.n_msg_slots - 1)


def test_predicate_read_sets(pass_result):
    summary, _ = pass_result
    reads = summary["predicates"]
    # TypeOK reads every packed field — the visibility condition that
    # (correctly) forbids pruning anything TypeOK-visible.
    from raft_tla_tpu.analysis.lane_map import FIELDS
    assert set(reads["TypeOK"]) == set(FIELDS)
    # The CONSTRAINT predicate's reads are exactly its bounded counters.
    assert set(reads["CONSTRAINT"]) == {"term", "log_len", "msg_cnt"}


@pytest.mark.parametrize("name, fields, msg_columns", [
    ("MessagesInv", ("term", "role", "log_len", "log_term", "log_val",
                     "msg_cnt", "msg"), 9),
    ("MoreUpToDateCorrect", ("commit", "log_len", "log_term", "log_val"),
     None),
])
def test_suite_read_sets_without_traced_index_reads(name, fields,
                                                    msg_columns):
    """The suite reads its tables at a traced position by compare, select
    and sum (models/safety.py ``_pick``), primitives the taint domain
    carries: the read sets are what the gathers gave (a gather at a
    traced index tainted the whole table too), element for element, and
    the domain falls back on no conservative rule."""
    from raft_tla_tpu.analysis.lane_map import field_shapes
    from raft_tla_tpu.models.safety import SAFETY_INVARIANTS
    reads, notes = por.predicate_read_sets(
        DIMS, [(name, SAFETY_INVARIANTS[name](DIMS))])
    assert notes == []
    want = {f: np.ones(field_shapes(DIMS)[f], bool) for f in fields}
    if msg_columns:     # header, mterm and the payload the four read
        want["msg"][:, msg_columns:] = False
    got = reads[name]
    assert set(got) == set(want)
    for f, mask in want.items():
        assert np.array_equal(np.asarray(got[f], bool), mask), f


def test_self_disabling_proof():
    """C3: a guard proved false on the kernel's own successor envelope.
    A one-shot toy action (guard ``role[0] == 0``, write ``role[0] = 1``)
    proves; Timeout (a candidate can time out again) must not."""
    from raft_tla_tpu.analysis.interp import trace_family, traced_kernels

    def one_shot(st):
        en = st.role[0] == 0
        succ = st._replace(
            role=jnp.where(jnp.arange(st.role.shape[0]) == 0, 1, st.role))
        return en, jnp.bool_(False), tuple(succ)

    closed = trace_family(one_shot, DIMS, 0)
    env = por._envelope_intervals(DIMS, BOUNDS)
    proved, _notes = por.self_disabling(closed, (), env)
    assert proved

    timeout_closed = next(c for name, c, _p in traced_kernels(DIMS)
                          if name == "Timeout")
    proved, _notes = por.self_disabling(timeout_closed, (0,), env)
    assert not proved


# ---------------------------------------------------------------------------
# Table integrity


def test_table_roundtrip_and_falsified_mask_rejected(real_table, tmp_path):
    path = tmp_path / "por.json"
    real_table.save(str(path))
    loaded = por.load_table(str(path))
    assert loaded.fingerprint == real_table.fingerprint
    assert loaded.certified == 0

    # Hand-edit the mask (certify instance 0) without refreshing the
    # fingerprint: the artifact must be rejected at load.
    doc = json.loads(path.read_text())
    doc["ample_mask"][0] = 1
    path.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        por.load_table(str(path))


def test_engine_rejects_falsified_artifact(real_table, tmp_path):
    """The engine-side gate of the same property: a tampered artifact
    never reaches the masking path."""
    path = tmp_path / "por.json"
    doc = real_table.to_json()
    doc["ample_mask"][0] = 1      # stale fingerprint now lies
    path.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        BFSEngine(DIMS, invariants={"TypeOK": build_type_ok(DIMS)},
                  constraint=build_constraint(DIMS, BOUNDS),
                  config=small_config(por_table=str(path)))


def test_table_v1_artifact_rejected(real_table):
    """A field-granular (version-1) artifact must be refused with a
    regenerate pointer: its certificates were proved under a coarser
    footprint encoding than the analyzer now emits."""
    doc = real_table.to_json()
    doc["version"] = 1
    doc.pop("granularity")
    with pytest.raises(ValueError, match="coarser footprint|regenerate"):
        por.PorTable.from_json(doc)
    doc2 = real_table.to_json()
    doc2["granularity"] = "field"
    with pytest.raises(ValueError, match="granularity"):
        por.PorTable.from_json(doc2)


def test_chunk_body_rejects_malformed_por_arrays():
    """The engine-side admission re-check at the compilation boundary:
    a mask that does not cover the instance grid (or carries the wrong
    dtype) fails before any lane is masked."""
    import jax.numpy as jnp
    from raft_tla_tpu.engine.chunk import build_chunk_body

    def build(mask, pri):
        return build_chunk_body(
            dims=DIMS, expand=None, fingerprint=None, pack_ok=None,
            inv_fns=None, constraint=None, B=8, G=DIMS.n_instances,
            K=8, Q=8, TQ=8, record_static=True, compactor=None,
            insert_fn=None, por_mask=mask, por_priority=pri)

    G = DIMS.n_instances
    with pytest.raises(ValueError, match="instance grid"):
        build(jnp.zeros(G - 1, jnp.bool_), jnp.zeros(G - 1, jnp.int32))
    with pytest.raises(ValueError, match="bool/int32"):
        build(jnp.zeros(G, jnp.int32), jnp.zeros(G, jnp.int32))
    with pytest.raises(ValueError, match="given together"):
        build(jnp.zeros(G, jnp.bool_), None)


def test_table_admission_checks(real_table):
    other = RaftDims(n_servers=2, n_values=1, max_log=2, n_msg_slots=4)
    with pytest.raises(ValueError, match="certified for model"):
        por.check_table(real_table, other)
    # A run checking an invariant outside the certified predicate set
    # must be rejected — its reads were never part of the visibility
    # condition.
    with pytest.raises(ValueError, match="NoLeader"):
        por.check_table(real_table, DIMS,
                        invariant_names=["TypeOK", "NoLeader"])
    # A forged certifying table without a CONSTRAINT predicate cannot be
    # applied to a constrained run.
    forged = forged_dup_table(predicates=("TypeOK",))
    with pytest.raises(ValueError, match="CONSTRAINT"):
        por.check_table(forged, DIMS, invariant_names=["TypeOK"],
                        has_constraint=True)


# ---------------------------------------------------------------------------
# Engine: POR-on vs POR-off (the oracle differential)


def test_por_smoke_on_off_counters(real_table):
    """The CI POR smoke: POR-on checking with the genuinely-certified
    (conservative, empty-mask) table is bit-identical to full expansion,
    and both match the Python oracle."""
    cons = build_constraint(DIMS, BOUNDS)
    inv = {"TypeOK": build_type_ok(DIMS)}
    off = BFSEngine(DIMS, invariants=inv, constraint=cons,
                    config=small_config()).run([init_state(DIMS)])
    on = BFSEngine(DIMS, invariants=inv, constraint=cons,
                   config=small_config(por_table=real_table)
                   ).run([init_state(DIMS)])
    assert on.por_instances == 0
    assert (on.distinct, on.generated, on.levels, on.diameter) \
        == (off.distinct, off.generated, off.levels, off.diameter)
    want = orc.bfs([init_state(DIMS)], DIMS,
                   invariants={"TypeOK": type_ok_py},
                   constraint=constraint_py(BOUNDS),
                   check_deadlock=False, max_levels=3)
    assert want.invariant_violation is None
    assert on.violation is None
    assert on.distinct == want.distinct_states
    assert on.levels == want.levels
    # Full coverage accounting still closes with the POR column at zero.
    assert sum(v["pruned"] for v in on.coverage.values()) == 0


def test_por_true_certifies_in_process():
    """EngineConfig.por=True runs the pass at engine build against this
    run's exact invariants + constraint; on Raft that yields the
    conservative empty mask and full-expansion counts."""
    cons = build_constraint(DIMS, BOUNDS)
    eng = BFSEngine(DIMS, invariants={"TypeOK": build_type_ok(DIMS)},
                    constraint=cons, config=small_config(por=True))
    assert eng._por_table is not None
    assert eng._por_table.certified == 0
    res = eng.run([init_state(DIMS)])
    assert res.por_instances == 0
    assert res.violation is None


def test_violation_still_found_with_por_on(real_table):
    """Verdict preservation on a violating model: the POR-on run must
    find the same invariant violation the oracle proves reachable, and
    its replayed counterexample must stay a legal spec path."""
    inv = {"TypeOK": build_type_ok(DIMS),
           "NoLeader": lambda st: jnp.all(st.role != LEADER)}
    # NoLeader is outside the table's certified predicates — admission
    # must reject the stale certificate...
    with pytest.raises(ValueError, match="NoLeader"):
        BFSEngine(DIMS, invariants=inv,
                  constraint=build_constraint(DIMS, BOUNDS),
                  config=small_config(por_table=real_table))
    # ...and in-process certification against the run's own invariant
    # set is the supported route.
    eng = BFSEngine(DIMS, invariants=inv,
                    constraint=build_constraint(DIMS, BOUNDS),
                    config=small_config(por=True))
    s0 = init_state(DIMS).replace(
        role=(1, 0, 0), current_term=(2, 2, 2), voted_for=(1, 1, 1),
        votes_responded=(0b001, 0, 0), votes_granted=(0b001, 0, 0),
        messages=frozenset({((1, 1, 0, 2, 1, ()), 1)}))
    res = eng.run([s0])
    assert res.stop_reason == "violation"
    assert res.violation.invariant == "NoLeader"
    want = orc.bfs([s0], DIMS,
                   invariants={"NoLeader": lambda s, d: LEADER not in s.role},
                   constraint=constraint_py(BOUNDS), check_deadlock=False)
    assert want.invariant_violation is not None
    steps = eng.replay(res.violation.fingerprint)
    for (s_prev, s_next) in zip(steps, steps[1:]):
        assert s_next[1] in orc.successor_set(s_prev[1], DIMS)


def test_forced_table_reduces_and_accounting_closes():
    """The masking machinery itself, driven by a forged certifying
    table: fewer generated/distinct states, the reduced distinct set is
    a SUBSET of the full run's, per-family accounting closes exactly,
    and the reduction is deterministic."""
    cons = build_constraint(DIMS, BOUNDS)
    inv = {"TypeOK": build_type_ok(DIMS)}
    full_eng = BFSEngine(DIMS, invariants=inv, constraint=cons,
                         config=small_config(record_trace=True))
    full = full_eng.run([init_state(DIMS)])
    table = forged_dup_table()
    red_eng = BFSEngine(DIMS, invariants=inv, constraint=cons,
                        config=small_config(record_trace=True,
                                            por_table=table))
    red = red_eng.run([init_state(DIMS)])
    assert red.por_instances == DIMS.n_msg_slots
    assert red.distinct < full.distinct
    assert red.generated < full.generated
    assert all(r <= f for r, f in zip(red.levels, full.levels))

    # Subset: every distinct state of the reduced run (trace fps plus
    # roots) appears in the full run's distinct set.
    full_fps = set(int(x) for x in full_eng.trace.export()[0]) \
        | set(full_eng.trace.roots)
    red_fps = set(int(x) for x in red_eng.trace.export()[0]) \
        | set(red_eng.trace.roots)
    assert red_fps <= full_fps

    # Reduced-vs-full accounting (obs/coverage.py): the expanded base
    # reconstructed from generated+disabled+pruned is one shared number
    # across families, and pruning actually happened.
    sizes = dict(zip(DIMS.family_names, DIMS.family_sizes))
    base = {n: (v["generated"] + v["disabled"] + v["pruned"]) / sizes[n]
            for n, v in red.coverage.items()}
    assert len(set(base.values())) == 1
    assert sum(v["pruned"] for v in red.coverage.values()) > 0
    # Pruned lanes concentrate outside the ample family by construction.
    assert red.coverage["DuplicateMessage"]["pruned"] == 0

    again = BFSEngine(DIMS, invariants=inv, constraint=cons,
                      config=small_config(record_trace=True,
                                          por_table=table)
                      ).run([init_state(DIMS)])
    assert (again.distinct, again.generated, again.levels) \
        == (red.distinct, red.generated, red.levels)


def test_forced_table_render_table_shows_pruned():
    """The run-end coverage table gains the pruned column only when the
    mask dropped something."""
    from raft_tla_tpu.obs import ActionCoverage
    cov = ActionCoverage(("A", "B"), (2, 3))
    cov.add_chunk(10, (5, 6), (1, 2))
    assert "pruned" not in cov.render_table()
    cov.add_chunk(0, (0, 0), (0, 0), (3, 0))
    out = cov.render_table()
    assert "POR pruned: 3" in out and "pruned" in out
    assert cov.disabled("A") == 10 * 2 - 5 - 3
    snap = cov.snapshot()
    assert snap["A"]["pruned"] == 3 and snap["B"]["pruned"] == 0


@pytest.mark.slow   # ~2 min CPU; tier-1 keeps the L0-L6 differentials
def test_oracle_differential_pinned_L0_L9(real_table):
    """The acceptance differential on the pinned MCraft_bounded L0-L9
    ground truths (scripts/oracle_exhaust.py, oracle_exhaust.jsonl
    level 9): a POR-on run with the genuinely-certified table matches
    the Python oracle's verdict and counts exactly.

    With the machine-checked impossibility result (zero certified on
    the Raft alphabet — see test_closure_block_is_machine_checked_
    impossible), POR-on IS full expansion, so distinct == full and
    every oracle state is reached by construction, with pruned == 0.
    If analyzer precision ever flips a family to certified, the same
    assertions become the real reduced-vs-full differential: the
    reduced run must still reproduce the full run's distinct-state
    count, levels, and verdict, now with pruned > 0 — the conditional
    branch below activates without edits here."""
    import os
    from raft_tla_tpu.engine.check import initial_states, make_engine
    from raft_tla_tpu.utils.cfg import load_config
    from tests.test_engine import MCRAFT_BOUNDED_LEVELS
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    setup = load_config(os.path.join(here, "configs/MCraft_bounded.cfg"))
    table = por.build_table(
        setup.dims, invariants={"TypeOK": build_type_ok(setup.dims)},
        constraint=build_constraint(setup.dims, setup.bounds))
    eng = make_engine(setup, EngineConfig(
        batch=512, queue_capacity=1 << 15, seen_capacity=1 << 20,
        check_deadlock=False, record_trace=False, sync_every=16,
        max_diameter=9, por_table=table))
    res = eng.run(initial_states(setup))
    # Pinned by the independent digest-based oracle sweep
    # (oracle_exhaust.jsonl level 9, 2026-07-29).
    assert res.levels == MCRAFT_BOUNDED_LEVELS[:10]
    assert res.distinct == 505004
    assert res.generated == 1421121
    assert res.violation is None          # oracle verdict: no violation
    assert res.por_instances == table.certified
    pruned = sum(v["pruned"] for v in res.coverage.values())
    if table.certified:
        # A newly certified family must show up as real reduction while
        # preserving the exhaustive result exactly (asserted above).
        assert pruned > 0
    else:
        assert pruned == 0
        # ... and the zero must be the machine-checked kind: the pass
        # proves the closure block inherent on this model too.
        summary, _f = por.analyze(
            setup.dims, bounds=setup.bounds,
            invariants={"TypeOK": build_type_ok(setup.dims)},
            constraint=build_constraint(setup.dims, setup.bounds),
            init_states=initial_states(setup))
        ref = summary["closure_refutation"]
        assert ref["ran"] and ref["open"] == []


# ---------------------------------------------------------------------------
# CLI


def test_cli_analyze_por_pass_and_artifact(tmp_path, capsys):
    from raft_tla_tpu.cli import main
    art = tmp_path / "por_table.json"
    rc = main(["analyze", "--max-log", "3", "--n-msg-slots", "4",
               "--passes", "effects,por", "--json",
               "--por-artifact", str(art)])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"]
    por_summary = rep["passes"]["por"]["summary"]
    assert por_summary["certified"] == 0
    assert por_summary["table"]["fingerprint"]
    warned = [f for f in rep["passes"]["por"]["findings"]
              if f["code"] == "por-widened"]
    assert warned
    table = por.load_table(str(art))      # artifact round-trips verified
    assert table.certified == 0


def test_cli_analyze_single_pass_resolves_deps(tmp_path, capsys):
    """`analyze --passes por` no longer requires the user to spell out
    the effects prerequisite: pass dependencies resolve topologically,
    the effects summary rides along in the report, and the text
    rendering carries the per-family POR table."""
    from raft_tla_tpu.cli import main
    rc = main(["analyze", "--max-log", "3", "--n-msg-slots", "4",
               "--passes", "por", "--json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"]
    assert {"effects", "por"} <= set(rep["passes"])
    assert rep["passes"]["effects"]["summary"]["independent_pairs"] > 0
    assert rep["passes"]["por"]["summary"]["certified"] == 0
    # Text mode: the rendered worklist table.
    rc = main(["analyze", "--max-log", "3", "--n-msg-slots", "4",
               "--passes", "por"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "top blocking element" in out
    assert "inherent" in out
    assert "closure refutation:" in out


def test_cli_analyze_unknown_pass_exits_2(tmp_path, capsys):
    from raft_tla_tpu.cli import main
    rc = main(["analyze", "--max-log", "3", "--n-msg-slots", "4",
               "--passes", "effects,typo"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "typo" in err and "por" in err and "effects" in err
    # Empty pass list is the same usage error, not a silent OK.
    rc = main(["analyze", "--max-log", "3", "--n-msg-slots", "4",
               "--passes", ","])
    assert rc == 2
    capsys.readouterr()


def test_cli_check_with_por_artifact(tmp_path, capsys):
    """check --por-table consumes the analyze-produced artifact end to
    end (the artifact workflow, tiny model)."""
    from raft_tla_tpu.cli import main
    cfg = tmp_path / "tiny.cfg"
    cfg.write_text(
        "CONSTANTS\n    Server = {r1, r2}\n    Value = {v1}\n"
        "    MaxTerm = 2\n    MaxLogLen = 1\n    MaxMsgCount = 1\n"
        "SPECIFICATION Spec\nINVARIANT TypeOK\nCONSTRAINT BoundedSpace\n"
        "CHECK_DEADLOCK FALSE\n"
        "\\* TPU: MAX_LOG = 2\n\\* TPU: N_MSG_SLOTS = 8\n")
    art = tmp_path / "por_table.json"
    rc = main(["analyze", str(cfg), "--passes", "effects,por",
               "--por-artifact", str(art)])
    assert rc == 0
    capsys.readouterr()
    rc = main(["check", str(cfg), "--platform", "cpu", "--batch", "32",
               "--max-diameter", "2", "--queue-capacity", "4096",
               "--seen-capacity", "32768", "--progress-interval", "0",
               "--por-table", str(art)])
    assert rc == 0
    assert "distinct states" in capsys.readouterr().out


def test_refutation_totals_exclude_certified_instances():
    """A certified instance has no non-commutation witness by
    construction — the witness tally must scope to closure-BLOCKED
    instances only, so a partially certified family never reads as
    'open' precision worklist (review finding on the aggregation)."""
    certified = por.Certificate(
        grid_index=0, family="X", label="X(i=0)",
        conditions={c: (True, "ok") for c in por.CONDITIONS})
    blocked = por.Certificate(
        grid_index=1, family="X", label="X(i=1)",
        conditions=dict({c: (True, "ok") for c in por.CONDITIONS},
                        closure=(False, "dependent")))
    refs = {"X(i=0)": por.ClosureRefutation("X(i=0)", "open"),
            "X(i=1)": por.ClosureRefutation(
                "X(i=1)", "witnessed", "Y(i=1)", "diamond", 0)}
    totals = por._refutation_totals([certified, blocked], refs)
    assert totals == {"ran": True, "witnessed": 1, "vacuous": 0,
                      "open": []}
    assert por._refutation_totals([certified, blocked], {}) \
        == {"ran": False, "witnessed": 0, "vacuous": 0, "open": []}

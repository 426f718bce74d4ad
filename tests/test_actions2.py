"""v2 (delta) pipeline vs v1 expand: bit-identical contract.

The v2 pipeline (models/actions2.py) must match v1 (models/actions.py +
ops/fingerprint.py + the chunk-level pack guard) EXACTLY — enabled and
overflow masks over the whole action grid, fingerprints, and every field
of every enabled successor — because the engines treat the two paths as
interchangeable (shared checkpoints, shared differential baselines).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.actions import build_expand
from raft_tla_tpu.models.actions2 import build_v2
from raft_tla_tpu.models.dims import CANDIDATE, LEADER
from raft_tla_tpu.models.invariants import constraint_py
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.models.schema import build_pack_guard, encode_state
from raft_tla_tpu.ops.fingerprint import build_fingerprint
from raft_tla_tpu.utils.cfg import load_config


def _build_rig(setup):
    dims = setup.dims
    expand = build_expand(dims)
    fp = build_fingerprint(dims)
    pack_ok = build_pack_guard(dims)
    v2 = build_v2(dims)
    G = dims.n_instances

    @jax.jit
    def v1_all(st):
        cands, en, ovf = expand(st)
        pk = jax.vmap(pack_ok)(cands)
        h, l = jax.vmap(fp)(cands)
        return cands, en, ovf | (en & ~pk), h, l

    @jax.jit
    def v2_all(st):
        en, ovf = v2.masks(st)
        ph = v2.parent_hash(st)
        h, l, succ = jax.vmap(v2.lane_out, (None, None, 0))(
            st, ph, jnp.arange(G, dtype=jnp.int32))
        phi, plo = v2.parent_fp(ph)
        return succ, en, ovf, h, l, phi, plo

    return setup, dims, jax.jit(fp), v1_all, v2_all


@pytest.fixture(scope="module")
def rig():
    return _build_rig(load_config("configs/MCraft_bounded.cfg"))


@pytest.fixture(scope="module")
def rig5():
    """The north star's widths (5 servers, MaxLogLen 4) with a small
    bag: where ``dvec``'s N-wide window was a per-lane loop on the TPU."""
    return _build_rig(load_config("configs/raft5_bounded.cfg",
                                  n_msg_slots=8))


def _assert_state_matches(rig_, s, ctx=""):
    setup, dims, fp1, v1_all, v2_all = rig_
    st = jax.tree.map(jnp.asarray, encode_state(s, dims))
    c1, en1, ovf1, h1, l1 = v1_all(st)
    c2, en2, ovf2, h2, l2, phi, plo = v2_all(st)
    rh, rl = fp1(st)
    assert (int(phi), int(plo)) == (int(rh), int(rl)), f"parent fp {ctx}"
    en1, en2, ovf1, ovf2 = map(np.asarray, (en1, en2, ovf1, ovf2))
    bad_en = np.nonzero(en1 != en2)[0]
    assert bad_en.size == 0, \
        f"enabled mismatch {ctx} at " \
        f"{[dims.describe_instance(int(g)) for g in bad_en[:4]]}"
    bad_ovf = np.nonzero(ovf1 != ovf2)[0]
    assert bad_ovf.size == 0, \
        f"overflow mismatch {ctx} at " \
        f"{[dims.describe_instance(int(g)) for g in bad_ovf[:4]]}"
    h1, l1, h2, l2 = map(np.asarray, (h1, l1, h2, l2))
    for g in np.nonzero(en1)[0]:
        gi = int(g)
        assert h1[g] == h2[g] and l1[g] == l2[g], \
            f"fp mismatch {ctx} {dims.describe_instance(gi)}"
        for name, a, b in zip(
                c1._fields,
                jax.tree.map(lambda a: np.asarray(a)[g], c1),
                jax.tree.map(lambda a: np.asarray(a)[g], c2)):
            assert (a == b).all(), \
                f"succ field {name} {ctx} {dims.describe_instance(gi)}"


@pytest.mark.parametrize("cfg, levels", [
    ("MCraft_bounded.cfg", 5), ("MCraft_noleader.cfg", 5),
    ("TPUraft.cfg", 3)], ids=["mcraft3", "noleader", "raft5"])
def test_v2_matches_v1_on_reachable_states(cfg, levels, rig):
    """v1 is v2's reference at every benchmark configuration's own dims
    and bounds: 473-, 403- and 951-byte rows (the last with the cfg's 48
    message slots and 224 instances)."""
    if cfg != "MCraft_bounded.cfg":
        rig = _build_rig(load_config("configs/" + cfg))
    setup, dims = rig[0], rig[1]
    res = orc.bfs([init_state(dims)], dims,
                  constraint=constraint_py(setup.bounds),
                  check_deadlock=False, max_levels=levels)
    states = list(res.parent)[-120:]
    assert len(states) >= 100
    for i, s in enumerate(states):
        _assert_state_matches(rig, s, ctx=f"reachable[{i}]")


def _row_delta_states(base, dims):
    """Leaders and an electable candidate whose ``next_index`` /
    ``match_index`` rows differ in every column, so Restart and
    BecomeLeader change whole rows and each of the N multipliers of the
    row's window weighs a different delta."""
    n, top = dims.n_servers, dims.max_log
    lead = base.role.index(LEADER)
    ni = tuple(tuple(1 + (2 * i + j + 1) % (top + 1) for j in range(n))
               for i in range(n))
    mi = tuple(tuple((3 * i + 2 * j + 1) % (top + 1) for j in range(n))
               for i in range(n))
    rows = base.replace(next_index=ni, match_index=mi)
    cand_log = ((1, 1), (base.current_term[lead], 2))
    cand = rows.replace(
        role=tuple(CANDIDATE if i == lead else r
                   for i, r in enumerate(rows.role)),
        votes_granted=tuple((1 << n) - 1 if i == lead else v
                            for i, v in enumerate(rows.votes_granted)),
        log=tuple(cand_log if i == lead else lg
                  for i, lg in enumerate(rows.log)))
    return [rows, cand]


@pytest.mark.parametrize("rig_name", ["rig", "rig5"])
def test_v2_matches_v1_on_leader_and_pack_edge_states(rig_name, request):
    rig = request.getfixturevalue(rig_name)
    setup, dims = rig[0], rig[1]
    n = dims.n_servers
    import sys
    sys.path.insert(0, "scripts")
    from leader_bench import leader_states
    extra = leader_states(dims, setup.bounds, 1)[:40]
    assert extra, "leader seeding failed"
    base = extra[0]
    s_cnt = orc.timeout(init_state(dims), dims, 0)
    mm = sorted(s_cnt.replace(messages=s_cnt.messages).messages)[0][0] \
        if s_cnt.messages else None
    crafted = [
        # term at the uint8 edge: Timeout must overflow-flag, not wrap.
        base.replace(current_term=(255,) * n),
        base.replace(current_term=(254,) + (255,) * (n - 1)),
        # lastLogTerm > 127 breaks the signed msg column 4: RequestVote
        # sends must overflow-flag (schema.build_pack_guard).
        base.replace(current_term=(200,) * n,
                     log=(((200, 1),), ((200, 2),)) + ((),) * (n - 2)),
    ] + _row_delta_states(base, dims)
    if mm is not None:
        crafted.append(s_cnt.replace(messages=frozenset({(mm, 255)})))
        crafted.append(s_cnt.replace(messages=frozenset({(mm, 254)})))
    # Bag at slot capacity: every send must take the overflow path
    # (enabled=False, overflow=True), and receives must still work.
    full_bag = frozenset(
        ((0, src, dst, t, 1, 0), 1)
        for src in range(dims.n_servers) for dst in range(dims.n_servers)
        for t in range(1, 1 + dims.n_msg_slots
                       // (dims.n_servers * dims.n_servers) + 1)
    )
    full_bag = frozenset(list(full_bag)[:dims.n_msg_slots])
    crafted.append(s_cnt.replace(messages=full_bag))
    for i, s in enumerate(extra + crafted):
        _assert_state_matches(rig, s, ctx=f"corner[{i}]")


def test_v2_rejects_unsupported_variant_dims():
    """A variant that declares extra families without v2 kernels must be
    rejected loudly (engines then fall back to v1 under 'auto')."""
    from raft_tla_tpu.models.dims import RaftDims

    class NoV2Dims(RaftDims):
        @property
        def extra_families(self):
            return (("Mystery", 2),)

    with pytest.raises(NotImplementedError):
        build_v2(NoV2Dims(n_servers=2, n_values=1, max_log=2,
                          n_msg_slots=8))


def test_v2_matches_v1_on_reconfig_variant():
    """The joint-consensus variant through the delta pipeline: bit-equal
    enabled/overflow/fingerprints/successors on leader states carrying
    real configuration entries (InitiateReconfig/FinalizeReconfig lanes
    included)."""
    import os
    import sys

    from raft_tla_tpu.models.invariants import constraint_py
    sys.path.insert(0, os.path.join(os.path.dirname(__file__),
                                    os.pardir, "scripts"))
    from leader_bench import leader_states

    setup = load_config("configs/reconfig3.cfg")
    dims, bounds = setup.dims, setup.bounds
    expand = build_expand(dims)
    fp = build_fingerprint(dims)
    pack_ok = build_pack_guard(dims)
    v2 = build_v2(dims)
    G = dims.n_instances

    @jax.jit
    def v1_all(st):
        cands, en, ovf = expand(st)
        pk = jax.vmap(pack_ok)(cands)
        h, l = jax.vmap(fp)(cands)
        return cands, en, ovf | (en & ~pk), h, l

    @jax.jit
    def v2_all(st):
        en, ovf = v2.masks(st)
        ph = v2.parent_hash(st)
        h, l, succ = jax.vmap(v2.lane_out, (None, None, 0))(
            st, ph, jnp.arange(G, dtype=jnp.int32))
        phi, plo = v2.parent_fp(ph)
        return succ, en, ovf, h, l, phi, plo

    rig_ = (setup, dims, jax.jit(fp), v1_all, v2_all)
    seeds = leader_states(dims, bounds, 0)
    assert seeds
    # grow a few levels so InitiateReconfig fires and its config entries
    # replicate; states WITH config entries must be among the parents
    res = orc.bfs(seeds, dims, constraint=constraint_py(bounds),
                  check_deadlock=False, max_levels=3)
    from raft_tla_tpu.models.reconfig import CFG_BASE
    states = list(res.parent)
    with_cfg = [s for s in states
                if any(e[1] >= CFG_BASE for lg in s.log for e in lg)]
    assert len(with_cfg) >= 10, "no config-entry states generated"
    for i, s in enumerate(with_cfg[:40] + states[:60]):
        _assert_state_matches(rig_, s, ctx=f"reconfig[{i}]")

    # Pack-edge parents: the guards-only extra masks reuse
    # pack_ok(parent) (reconfig.build_extra_masks_v2), so the ~pack_ok
    # branch of the EXTRA lanes' overflow must match the v1 evaluation
    # (en & ~pack_ok(successor)) even on unpackable parents.  Engine
    # parents are always packable (they come from uint8 rows) and the
    # core v2 masks rely on that, so only the extra lanes are compared
    # here; force the edge by pushing a term past the uint8 bound.
    n_extra = sum(size for _name, size in dims.extra_families)
    lo = dims.n_instances - n_extra
    for i, s in enumerate(with_cfg[:6]):
        edge = s.replace(current_term=(256,) + s.current_term[1:])
        st = jax.tree.map(jnp.asarray, encode_state(edge, dims))
        _c1, en1, ovf1, _h1, _l1 = v1_all(st)
        _c2, en2, ovf2, _h2, _l2, _p, _q = v2_all(st)
        assert (np.asarray(en1)[lo:] == np.asarray(en2)[lo:]).all(), \
            f"pack-edge[{i}] extra enabled"
        assert (np.asarray(ovf1)[lo:] == np.asarray(ovf2)[lo:]).all(), \
            f"pack-edge[{i}] extra overflow"


def test_extra_masks_v2_shape_mismatch_rejected():
    """A variant whose build_extra_masks_v2 disagrees with its family
    count must fail at build time, not silently mis-zip kernels."""
    from raft_tla_tpu.models.reconfig import ReconfigDims

    class BadMasks(ReconfigDims):
        def build_extra_masks_v2(self):
            return super().build_extra_masks_v2()[:1]

    setup = load_config("configs/reconfig3.cfg")
    d = setup.dims
    with pytest.raises(ValueError, match="build_extra_masks_v2"):
        build_v2(BadMasks(n_servers=d.n_servers, n_values=d.n_values,
                          max_log=d.max_log, n_msg_slots=d.n_msg_slots,
                          targets=d.targets))


def test_auto_pipeline_propagates_accidental_errors():
    """pipeline='auto' falls back to v1 ONLY on V2Unavailable (the
    dedicated no-v2-kernels signal); an accidental NotImplementedError
    deep inside a variant's build_extra_v2 must propagate, not silently
    select the slow path (advisor r4).  The resolved pipeline is
    recorded on EngineResult so fallbacks are observable."""
    from raft_tla_tpu.engine.bfs import _resolve_pipeline
    from raft_tla_tpu.models.actions2 import V2Unavailable
    from raft_tla_tpu.models.dims import RaftDims

    base = RaftDims(n_servers=2, n_values=1, max_log=2, n_msg_slots=8)
    assert _resolve_pipeline("auto", base) is not None   # base dims -> v2

    class NoV2(RaftDims):
        @property
        def extra_families(self):
            return (("Mystery", 2),)

    nov2 = NoV2(n_servers=2, n_values=1, max_log=2, n_msg_slots=8)
    with pytest.raises(V2Unavailable):
        build_v2(nov2)
    assert _resolve_pipeline("auto", nov2) is None       # clean fallback

    class Buggy(RaftDims):
        def build_extra_v2(self, fp_helpers):
            raise NotImplementedError("accidental: unfinished kernel")

    with pytest.raises(NotImplementedError, match="accidental"):
        _resolve_pipeline("auto",
                          Buggy(n_servers=2, n_values=1, max_log=2,
                                n_msg_slots=8))


def test_simulator_pipelines_agree_seeded():
    """engine/simulate.py: v1 and v2 walker fleets draw identical actions
    (masks are bit-identical), so a seeded run's step/trace/violation
    accounting must agree exactly across pipelines."""
    from raft_tla_tpu.engine.simulate import Simulator
    from raft_tla_tpu.models.invariants import (build_constraint,
                                                build_type_ok)
    setup = load_config("configs/MCraft_bounded.cfg")
    dims = setup.dims
    roots = [init_state(dims)]
    kw = dict(invariants={"TypeOK": build_type_ok(dims)},
              constraint=build_constraint(dims, setup.bounds),
              batch=32, depth=16, chunk=8)
    r1 = Simulator(dims, pipeline="v1", **kw).run(roots, 512, seed=11)
    r2 = Simulator(dims, pipeline="v2", **kw).run(roots, 512, seed=11)
    assert (r1.steps, r1.traces, r1.violation_invariant) \
        == (r2.steps, r2.traces, r2.violation_invariant)


@pytest.mark.parametrize("cfg", [
    "MCraft_bounded.cfg", "MCraft_noleader.cfg", "MCraft_safety.cfg",
    "TPUraft.cfg", "raft5_bounded.cfg"])
def test_auto_runs_v2_on_every_base_alphabet_cfg(cfg):
    """``EngineResult.pipeline`` — what the benchmark's ``correct`` reads —
    is ``v2`` under ``auto`` for every cfg in ``configs/`` with the base
    action alphabet, whatever its widths and invariant set."""
    from raft_tla_tpu.engine.bfs import EngineConfig
    from raft_tla_tpu.engine.check import initial_states, make_engine
    setup = load_config("configs/" + cfg)
    assert not setup.dims.extra_families
    eng = make_engine(setup, EngineConfig(
        batch=32, queue_capacity=1 << 12, seen_capacity=1 << 14,
        record_trace=False, max_diameter=2))
    assert eng.config.pipeline == "auto"
    res = eng.run(initial_states(setup))
    assert res.pipeline == "v2" and res.diameter == 2


# -- the variant's declared appends, written through lane_out's one log
# -- append (models/dims.py LogAppend) ----------------------------------------

def _general_form_dims(dims):
    """``dims``' two families as general ``lane_fn`` entries, the other
    form ``build_extra_v2`` may return: the kernels ``ReconfigDims`` gave
    until it declared both a ``LogAppend``."""
    from raft_tla_tpu.models.reconfig import ReconfigDims

    class GeneralForm(ReconfigDims):
        def build_extra_v2(self, fp):
            (_e0, init_val), (_e1, fin_val) = self._build_guards()
            top = self.max_log

            def append_delta_succ(st, i, val):
                ln = st.log_len[i]
                k = jnp.clip(ln, 0, top - 1)
                d_base = fp.dsum(
                    fp.dpos(fp.O_LT + i * top + k, st.log_term[i, k],
                            st.term[i]),
                    fp.dpos(fp.O_LV + i * top + k, st.log_val[i, k], val),
                    fp.dpos(fp.O_LL + i, ln, ln + 1))
                _fits, succ = self._append_entry(st, i, val)
                return d_base, fp.ZD, succ

            return [lambda st, i, c: append_delta_succ(
                        st, i, init_val(st, i, c)),
                    lambda st, i: append_delta_succ(st, i, fin_val(st, i))]

    return GeneralForm(**{f.name: getattr(dims, f.name)
                          for f in dataclasses.fields(dims)})


def _fold_states(dims):
    """name -> encoded parent.  The nine canonical roots of the
    benchmark's ``reconfig3`` cell (``E_i`` elected, ``J_i`` joint entry
    committed, ``F_i`` finalized) by the recipe on the program's own
    oracle, and the corners of the append: a log at ``MaxLogLen`` and a
    full one (``log_len == max_log``: the lane is disabled and flagged),
    parents past the row's packing, and a log whose truncated
    configuration entry still lies beyond its length."""
    from raft_tla_tpu.models.reconfig import final_value, joint_value
    from tests.test_reconfig_deployment import recipe_on_the_programs_oracle

    named = {}
    for i in range(dims.n_servers):
        for name, path in recipe_on_the_programs_oracle(dims, i).items():
            named[name] = path[-1]
    e0, j0, j1 = named["E_0"], named["J_0"], named["J_1"]
    t0 = e0.current_term[0]

    def with_log0(s, log):
        return s.replace(log=(tuple(log),) + s.log[1:])

    client = (t0, 1)
    named["at_max_log_len"] = with_log0(e0, [client] * 2)
    named["full_log_final"] = with_log0(e0, [client] * dims.max_log)
    named["full_log_joint"] = with_log0(
        j0, [j0.log[0][0]] + [client] * (dims.max_log - 1))
    named["pack_edge_final"] = e0.replace(
        current_term=(256,) + e0.current_term[1:])
    named["pack_edge_joint"] = j1.replace(
        current_term=j1.current_term[:1] + (256,) + j1.current_term[2:])
    states = {k: jax.tree.map(jnp.asarray, encode_state(s, dims))
              for k, s in named.items()}
    # C_3 at index 1; the joint entry C_3,7 that followed it was
    # truncated away and its value left in the tensor past the length.
    st = jax.tree.map(np.array, encode_state(
        with_log0(e0, [(t0, final_value(3))]), dims))
    st.log_term[0, 1], st.log_val[0, 1] = t0, joint_value(3, 7)
    states["truncated_cfg"] = jax.tree.map(jnp.asarray, st)
    return states


FOLD_STATES = [f"{kind}_{i}" for i in range(3) for kind in "EJF"] + [
    "at_max_log_len", "full_log_final", "full_log_joint",
    "pack_edge_final", "pack_edge_joint", "truncated_cfg"]


@pytest.fixture(scope="module")
def fold_rig():
    """(dims, states, v1 over the grid, v2 with the declared appends, v2
    with the same families as general lane_fns) on ``reconfig3.cfg``."""
    setup = load_config("configs/reconfig3.cfg")
    dims = setup.dims
    _s, _d, _fp, v1_all, v2_all = _build_rig(setup)
    general = _build_rig(dataclasses.replace(
        setup, dims=_general_form_dims(dims)))[4]
    states = _fold_states(dims)
    assert sorted(states) == sorted(FOLD_STATES)
    return dims, states, v1_all, v2_all, general


@pytest.mark.parametrize("name", FOLD_STATES)
@pytest.mark.parametrize("family", ["InitiateReconfig", "FinalizeReconfig"])
def test_a_declared_append_is_the_v1_lane_and_the_oracles(fold_rig, family,
                                                          name):
    """On every lane of the family: masks as v1's; where enabled,
    ``lane_out``'s successor field for field and its delta key equal to
    the v1 kernel's successor and that successor's full fingerprint, and
    the decoded successor the one ``extra_successors_py`` gives for the
    instance."""
    from raft_tla_tpu.models.schema import StateBatch, decode_state
    dims, states, v1_all, v2_all, _general = fold_rig
    st = states[name]
    parent = decode_state(jax.tree.map(np.asarray, st), dims)
    c1, en1, ovf1, h1, l1 = jax.device_get(v1_all(st))
    c2, en2, ovf2, h2, l2, _phi, _plo = jax.device_get(v2_all(st))
    fam = dims.family_names.index(family)
    off, size = dims.family_offsets[fam], dims.family_sizes[fam]
    want = {a: t for a, t in dims.extra_successors_py(parent)
            if a[0] == fam}
    for g in range(off, off + size):
        where = f"{name} {dims.describe_instance(g)}"
        assert (en1[g], ovf1[g]) == (en2[g], ovf2[g]), where
        code, params = dims.instance_info(g)
        key = (code, tuple(params.values()))
        fits = len(parent.log[params["i"]]) < dims.max_log
        assert bool(en2[g]) == (key in want and fits), where
        assert bool(ovf2[g]) >= (key in want and not fits), where
        if not en2[g]:
            continue
        assert (h1[g], l1[g]) == (h2[g], l2[g]), where
        for field, a, b in zip(c1._fields, c1, c2):
            assert (a[g] == b[g]).all(), f"{field} {where}"
        succ = decode_state(StateBatch(*(a[g] for a in c2)), dims)
        assert succ == want[key], where


@pytest.mark.parametrize("name", FOLD_STATES)
def test_the_general_lane_fn_form_gives_the_same_lanes(fold_rig, name):
    """``build_extra_v2``'s two forms of the same two families: the same
    masks, and on every enabled lane of the whole grid the same key and
    successor.  The general form stays a supported path."""
    dims, states, _v1, v2_all, general = fold_rig
    got = jax.device_get(v2_all(states[name]))
    ref = jax.device_get(general(states[name]))
    for a, b in zip(got[1:3] + got[5:], ref[1:3] + ref[5:]):
        assert (np.asarray(a) == np.asarray(b)).all()
    extra = dims.family_offsets[10]
    assert got[1][extra:].any() or name.startswith("full_log")
    for g in np.nonzero(got[1])[0]:
        where = f"{name} {dims.describe_instance(int(g))}"
        assert (got[3][g], got[4][g]) == (ref[3][g], ref[4][g]), where
        for field, a, b in zip(got[0]._fields, got[0], ref[0]):
            assert (a[g] == b[g]).all(), f"{field} {where}"


def test_a_declared_append_takes_a_server_and_at_most_one_more():
    """The decode tables hold (i, one more) an instance: a declaration
    over three parameter arrays is refused when the pipeline is built."""
    from raft_tla_tpu.models.reconfig import ReconfigDims

    class ThreeParams(ReconfigDims):
        def build_extra_kernels(self):
            (params, kern), fin = super().build_extra_kernels()
            return [(params + params[:1], kern), fin]

    d = load_config("configs/reconfig3.cfg").dims
    with pytest.raises(ValueError, match="LogAppend"):
        build_v2(ThreeParams(n_servers=d.n_servers, n_values=d.n_values,
                             max_log=d.max_log, n_msg_slots=d.n_msg_slots,
                             targets=d.targets))

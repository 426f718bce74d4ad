"""``TypeOK``'s truth table, and the shape its speed on the chip rests on.

``models/invariants.py build_type_ok`` is eleven content checks the
fixed-width encoding does not force (raft.tla:482-492).  Held here over
the three ``dims`` the cfgs that name ``TypeOK`` use (3-server bounded,
the 5-server ``TPUraft.cfg``, ``reconfig3.cfg``'s widened ``Value``):

- reachable states of a short walk all pass, as ``type_ok_py`` says;
- for every check one mutated state on which that check ALONE fails (by
  the plain per-state reference below, which keeps the ``all``-of-cells
  spelling the kernel had until PR 38) reads ``False``, alone and under
  ``vmap`` in a batch of sound states, and ``build_inv_id`` names it;
- every reduction of the vmapped kernel is an int32 ``reduce_sum``: an
  and-reduction over a ``[K, N, L]`` boolean lets the TPU's compiler
  keep the lanes major (3.9 ms a pass of ``mcraft3-deep``, PERF.md §6).
"""

import functools
import os
import random

import jax
import numpy as np
import pytest

from raft_tla_tpu.analysis.lint import _walk_eqns
from raft_tla_tpu.models import oracle as orc
from raft_tla_tpu.models.invariants import (build_inv_id, build_type_ok,
                                            type_ok_py)
from raft_tla_tpu.models.pystate import init_state
from raft_tla_tpu.models.schema import StateBatch, encode_state, stack_states
from raft_tla_tpu.utils.cfg import load_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFGS = ("MCraft_bounded", "TPUraft", "reconfig3")


@functools.lru_cache(maxsize=None)
def _dims(cfg):
    return load_config(os.path.join(REPO, f"configs/{cfg}.cfg")).dims


@functools.lru_cache(maxsize=None)
def _kernel(cfg):
    return build_type_ok(_dims(cfg))


@functools.lru_cache(maxsize=None)
def _named(cfg):
    """``build_inv_id`` over a batch, jitted as every engine runs it.  Run
    eagerly under ``vmap``, its ``jnp.int32(...)`` constants reach the
    process-wide ``convert_element_type`` callable from inside a batch
    trace, jax keeps no fast path for a call made there, and where that
    is the process's FIRST such call the signature stays on pjit's
    Python path for good: every later ``jnp.int32(0)`` of any engine in
    this worker then counts as a trace (tests/test_spans.py's warm run
    read 33 of them whenever this file ran before it)."""
    return jax.jit(jax.vmap(build_inv_id([_kernel(cfg)])))


@functools.lru_cache(maxsize=None)
def _walk(dims, steps=40, seeds=(1, 2, 3)):
    """States of a few random walks from the initial state, kept inside
    what a row can hold (term and bag under the pack guard's limits)."""
    seen = [init_state(dims)]
    for seed in seeds:
        rng, s = random.Random(seed), seen[0]
        for _ in range(steps):
            nxt = sorted(
                (t for t in orc.successor_set(s, dims)
                 if max(t.current_term) <= 4
                 and len(t.messages) < dims.n_msg_slots
                 and all(c <= 2 for _m, c in t.messages)),
                key=repr)
            if not nxt:
                break
            s = rng.choice(nxt)
            seen.append(s)
    return tuple(seen)


def _reference_checks(st, dims):
    """The eleven checks on ONE encoded state, in ``build_type_ok``'s
    order, as plain numpy ``all``s."""
    N, L = dims.n_servers, dims.max_log
    in_log = np.arange(L)[None, :] < st.log_len[:, None]
    value_ok = np.vectorize(dims.value_ok_py)(st.log_val)
    occ = st.msg_cnt > 0
    mt, src, dst = st.msg[:, 0], st.msg[:, 1], st.msg[:, 2]
    return [
        np.all((st.role >= 0) & (st.role <= 2)),
        np.all((st.voted_for >= 0) & (st.voted_for <= N)),
        np.all(np.where(in_log, (st.log_term >= 0) & value_ok,
                        (st.log_term == 0) & (st.log_val == 0))),
        np.all((st.log_len >= 0) & (st.log_len <= L)),
        np.all(st.term >= 0) and np.all(st.commit >= 0),
        np.all((st.votes_resp >= 0) & (st.votes_resp < (1 << N))),
        np.all((st.votes_gran >= 0) & (st.votes_gran < (1 << N))),
        np.all(st.next_idx >= 1),
        np.all(st.match_idx >= 0),
        np.all(np.where(occ, (mt >= 1) & (mt <= 4) & (src >= 1) & (src <= N)
                        & (dst >= 1) & (dst <= N) & (st.msg[:, 3] >= 0),
                        np.all(st.msg == 0, axis=1))),
        np.all(st.msg_cnt >= 0),
    ]


# (id, the one check of the eleven it breaks, field, index, value).  Every
# base state has an entry in server 0's log and a message in slot 0.
MUTATIONS = [
    ("role", 0, "role", (0,), 3),
    ("voted_for", 1, "voted_for", (0,), lambda d: d.n_servers + 1),
    ("log_entry_value", 2, "log_val", (0, 0), 0),
    ("log_entry_term", 2, "log_term", (0, 0), -1),
    ("log_tail", 2, "log_val", (1, lambda d: d.max_log - 1), 1),
    ("log_len", 3, "log_len", (1,), -1),
    ("term", 4, "term", (0,), -1),
    ("commit", 4, "commit", (1,), -1),
    ("votes_resp", 5, "votes_resp", (0,), lambda d: 1 << d.n_servers),
    ("votes_gran", 6, "votes_gran", (1,), -1),
    ("next_idx", 7, "next_idx", (0, 1), 0),
    ("match_idx", 8, "match_idx", (1, 0), -1),
    ("msg_type", 9, "msg", (0, 0), 5),
    ("msg_dest", 9, "msg", (0, 2), lambda d: d.n_servers + 1),
    ("msg_empty_slot", 9, "msg", (lambda d: d.n_msg_slots - 1, 5), 1),
    ("msg_cnt", 10, "msg_cnt", (lambda d: d.n_msg_slots - 1,), -1),
]


def _base(dims):
    """A sound encoded state with something in every field a mutation
    touches: an entry in server 0's log, one message in slot 0."""
    s = init_state(dims)
    st = encode_state(s.replace(log=(((1, 1),),) + s.log[1:]), dims)
    st.msg[0, :4] = (1, 1, 2, 1)
    st.msg_cnt[0] = 1
    return st


def _mutated(dims, field, index, value):
    def of(x):
        return x(dims) if callable(x) else x
    st = StateBatch(*(a.copy() for a in _base(dims)))
    getattr(st, field)[tuple(of(i) for i in index)] = of(value)
    return st


@pytest.mark.parametrize("cfg", CFGS)
def test_reachable_states_pass_as_the_oracle_says(cfg):
    dims = _dims(cfg)
    states = _walk(dims)
    assert len(states) > 60
    assert len({s.role for s in states}) > 3      # the walk went somewhere
    got = np.asarray(jax.vmap(_kernel(cfg))(
        stack_states([encode_state(s, dims) for s in states])))
    assert got.tolist() == [type_ok_py(s, dims) for s in states]
    assert got.all()


@pytest.mark.parametrize("name, check, field, index, value", MUTATIONS,
                         ids=[m[0] for m in MUTATIONS])
@pytest.mark.parametrize("cfg", CFGS)
def test_a_state_that_breaks_one_check_reads_false(cfg, name, check, field,
                                                   index, value):
    dims, type_ok = _dims(cfg), _kernel(cfg)
    base, bad = _base(dims), _mutated(dims, field, index, value)
    assert all(_reference_checks(base, dims))
    assert [i for i, ok in enumerate(_reference_checks(bad, dims))
            if not ok] == [check]
    assert bool(type_ok(base)) and not bool(type_ok(bad))
    sound = [encode_state(s, dims) for s in _walk(dims, steps=6, seeds=(5,))]
    batch = stack_states([*sound[:3], bad, *sound[3:], base])
    want = [True] * 3 + [False] + [True] * (len(sound) - 3) + [True]
    assert np.asarray(jax.vmap(type_ok)(batch)).tolist() == want
    named = _named(cfg)(batch)
    assert np.asarray(named).tolist() == [-1 if ok else 0 for ok in want]


@pytest.mark.parametrize("cfg", CFGS)
def test_every_reduction_over_the_lanes_fields_is_an_integer_sum(cfg):
    """What the chip's speed rests on (PERF.md §6, PR 38): XLA lays a
    ``[K, N, L]`` field out lanes-minor for an integer sum over its
    axes, and leaves it as its producer made it (the parents' gather:
    lanes major, an axis of 3 padded to 128) for a ``reduce_and``."""
    dims = _dims(cfg)
    batch = stack_states([_base(dims)] * 4)
    jaxpr = jax.make_jaxpr(jax.vmap(_kernel(cfg)))(batch)
    reductions = [eqn for eqn in _walk_eqns(jaxpr)
                  if eqn.primitive.name.startswith(("reduce_", "arg", "cum"))]
    assert len(reductions) >= 11, reductions    # else the walk saw nothing
    for eqn in reductions:
        assert eqn.primitive.name == "reduce_sum", eqn
        assert eqn.invars[0].aval.dtype == np.int32, eqn
        assert eqn.outvars[0].aval.shape[0] == 4, eqn     # the lanes stay
    booleans = [v.aval for eqn in _walk_eqns(jaxpr) for v in eqn.outvars
                if v.aval.dtype == np.bool_]
    assert max(a.ndim for a in booleans) == 3   # [K, N, L] compares: seen

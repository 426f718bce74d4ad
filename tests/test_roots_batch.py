"""The roots of a run prepared as one batch (``engine/bfs.py _run_impl``'s
prologue: ``stack_states`` once, ``check_packable`` and ``flatten_states``
on the stack) against the same roots prepared one at a time, as every
engine did until PR 48: the rows that reach ``ingest``, the error an
unpackable root raises, the violation that outranks it, and the keys the
trace store keeps the roots under.

CPU, small sizes.  Root sets: ``MCraft_bounded.cfg``'s ``Init`` (one), the
117 leader-holding roots of ``benchmark/reference/leaders.py``, the nine of
``reference/reconfig.py`` (two-byte value lanes) and ``Smokeraft.cfg``'s
512 under seeds 1-3.
"""

import dataclasses
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import bench_lib as lib  # noqa: E402
from raft_tla_tpu.engine.bfs import EngineConfig  # noqa: E402
from raft_tla_tpu.engine.check import initial_states, make_engine  # noqa: E402
from raft_tla_tpu.models.pystate import PyState  # noqa: E402
from raft_tla_tpu.models.schema import (StateBatch,  # noqa: E402
                                        check_packable, encode_state,
                                        flatten_state, flatten_states,
                                        stack_states)
from raft_tla_tpu.ops.fingerprint import build_fingerprint  # noqa: E402
from raft_tla_tpu.utils.cfg import load_config  # noqa: E402
from reference import leaders  # noqa: E402
from reference import reconfig as ref_reconfig  # noqa: E402

SETS = ("init", "leaders", "reconfig", "smoke-1", "smoke-2", "smoke-3")
COUNTS = {"init": 1, "leaders": 117, "reconfig": 9,
          "smoke-1": 512, "smoke-2": 512, "smoke-3": 512}


def cfg_path(name: str) -> str:
    return os.path.join(REPO, "configs", name)


def to_program(s) -> PyState:
    return PyState(**{f.name: getattr(s, f.name)
                      for f in dataclasses.fields(PyState)})


@pytest.fixture(scope="module")
def root_sets():
    """name -> (setup, roots)."""
    out = {}
    setup = load_config(cfg_path("MCraft_bounded.cfg"))
    out["init"] = (setup, initial_states(setup))
    config = lib.load_json("configs", "mcraft3-safety.json")
    setup = load_config(cfg_path(config["cfg_name"]),
                        n_msg_slots=config["n_msg_slots"])
    out["leaders"] = (setup, [to_program(r.state) for r in
                              leaders.leader_roots(
                                  leaders.reference_dims(config))])
    config = lib.load_json("configs", "reconfig3.json")
    setup = load_config(cfg_path("reconfig3.cfg"),
                        n_msg_slots=config["n_msg_slots"])
    out["reconfig"] = (setup, [to_program(r.state) for r in
                               ref_reconfig.canonical_roots(
                                   ref_reconfig.reference_dims(config))])
    setup = load_config(cfg_path("Smokeraft.cfg"))
    for seed in (1, 2, 3):
        out[f"smoke-{seed}"] = (setup, initial_states(setup, seed=seed))
    return out


def one_at_a_time(roots, dims) -> np.ndarray:
    return np.stack([flatten_state(encode_state(s, dims), dims)
                     for s in roots])


# -- the rows -----------------------------------------------------------------

@pytest.mark.parametrize("name", SETS)
def test_the_batch_rows_are_the_single_rows_byte_for_byte(root_sets, name):
    setup, roots = root_sets[name]
    dims = setup.dims
    assert len(roots) == COUNTS[name]
    assert (dims.value_bytes == 2) == (name == "reconfig")
    stacked = stack_states([encode_state(s, dims) for s in roots])
    check_packable(stacked, dims)
    rows = flatten_states(stacked, dims)
    want = one_at_a_time(roots, dims)
    assert rows.dtype == want.dtype == np.uint8
    assert rows.shape == want.shape and rows.tobytes() == want.tobytes()
    if name == "reconfig":
        # The high-byte planes are in use: a configuration entry's value.
        assert stacked.log_val.max() > 255


# -- an unpackable root ---------------------------------------------------------

def spoil(st: StateBatch, field: str, index: tuple, value: int) -> StateBatch:
    arr = np.array(getattr(st, field))
    arr[index] = value
    return st._replace(**{field: arr})


@pytest.mark.parametrize("name,field,index,value,says", [
    ("init", "term", (1,), 300, r"term.*Timeout, Receive"),
    ("leaders", "msg", (1, 4), 200,
     r"slot 1 column 4.*mlastLogTerm.*RequestVote"),
    ("leaders", "msg", (0, 4), -129, r"slot 0 column 4.*\[-128, 127\]"),
    ("reconfig", "log_val", (2, 0), 65536, r"log_val.*\[0, 65535\]"),
    ("reconfig", "msg", (0, 8), 70000, r"slot 0 column 8.*\[0, 65535\]"),
    ("smoke-1", "msg_cnt", (3,), 256, r"msg_cnt.*\[0, 255\]"),
    ("smoke-2", "match_idx", (2, 1), -1, r"match_idx.*at index \(2, 1\)"),
])
def test_an_unpackable_root_is_named_by_field_lane_and_number(
        root_sets, name, field, index, value, says):
    setup, roots = root_sets[name]
    dims = setup.dims
    encoded = [encode_state(s, dims) for s in roots]
    # The root at fault is not the first, and a later one is at fault in
    # an EARLIER field: the first root still wins, as the loop over the
    # roots had it.
    at = len(encoded) // 2
    encoded[at] = spoil(encoded[at], field, index, value)
    encoded.append(spoil(encoded[0], "term", (0,), 999))
    with pytest.raises(ValueError) as single:
        check_packable(encoded[at], dims)
    with pytest.raises(ValueError, match=says) as batch:
        check_packable(stack_states(encoded), dims)
    assert str(batch.value) == f"root {at}: {single.value}"
    assert not str(single.value).startswith("root")


# -- a violation outranks the error -----------------------------------------------

def small(**kw) -> EngineConfig:
    return EngineConfig(batch=64, queue_capacity=1 << 14,
                        seen_capacity=1 << 17, **kw)


@pytest.fixture(scope="module")
def bounded(root_sets):
    setup, (root,) = root_sets["init"]
    return setup, root, make_engine(setup, small(max_diameter=1))


def flagged(root: PyState) -> PyState:
    """``matchIndex = -1``: ``TypeOK`` fails, and the row would alias."""
    return dataclasses.replace(
        root, match_index=((0, -1, 0),) + tuple(root.match_index[1:]))


def unpackable(root: PyState) -> PyState:
    """A term of 300 is a ``Nat``: ``TypeOK`` holds, the byte does not."""
    return dataclasses.replace(root, current_term=(0, 300, 0))


@pytest.mark.parametrize("order", ["error_first", "violation_first"])
def test_a_flagged_root_is_the_violation_not_the_error(bounded, order):
    setup, root, eng = bounded
    roots = [root, unpackable(root), root, flagged(root)]
    if order == "violation_first":
        roots.reverse()
    res = eng.run(roots)
    assert res.stop_reason == "violation" and res.levels == [0]
    assert res.violation.invariant == "TypeOK"
    assert res.violation.state == flagged(root)
    assert eng.replay(res.violation.fingerprint) == [(-1, flagged(root))]
    # Without the flagged root the same batch is the error, by number.
    roots.remove(flagged(root))
    with pytest.raises(ValueError, match=r"^root 1: value 300 at state "
                                         r"field 'term'"):
        eng.run(roots)


# -- the trace store's roots ------------------------------------------------------

@pytest.mark.parametrize("name", ["init", "reconfig", "smoke-3"])
def test_the_trace_store_keeps_the_roots_under_the_same_keys(root_sets,
                                                             name):
    """A run from the roots (a batch of 64: the root check's last chunk is
    padded except under 512) registers each root under the fingerprint of
    its own encoding, and ingests the rows of the single-state path."""
    setup, roots = root_sets[name]
    dims = setup.dims
    fp = jax.jit(jax.vmap(build_fingerprint(dims)))
    hi, lo = (np.asarray(x) for x in fp(
        stack_states([encode_state(s, dims) for s in roots])))
    want = {(int(h) << 32) | int(l): s for h, l, s in zip(hi, lo, roots)}
    assert len(want) == len(roots)
    eng = make_engine(setup, small(max_diameter=0))
    seen_rows = []
    ingest = eng._ingest
    eng._ingest = lambda rows, valid, *rest: (
        seen_rows.append(np.asarray(rows)[np.asarray(valid)]),
        ingest(rows, valid, *rest))[1]
    res = eng.run(roots)
    assert res.levels[0] == res.distinct == len(roots)
    assert eng.trace.roots == want
    got = np.concatenate([r for r in seen_rows if len(r)])
    assert got.tobytes() == one_at_a_time(roots, dims).tobytes()
    phases = eng.metrics.phase_seconds()
    assert phases["roots_encode"] > 0 and phases["root_check"] > 0

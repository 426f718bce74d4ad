"""cfg-parser tests: the reference configs are the source of truth."""

import os

import pytest

from raft_tla_tpu.utils.cfg import (load_config, parse_cfg,
                                    scan_module_definitions)

REF = "/root/reference"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def reference():
    """Path to the read-only reference spec checkout, or a skip.

    The reference (lemmy/raft.tla + TLC harness configs) is mounted at
    /root/reference on the primary dev host but absent in plain CI /
    test containers; the tests that parse the REAL reference files
    skip there with this reason instead of failing tier-1.  Everything
    those tests cover structurally is still exercised against the
    committed configs/ copies by the rest of this module."""
    if not os.path.isdir(REF):
        pytest.skip(f"reference specs not mounted ({REF} absent in this "
                    f"container); committed configs/ cover the grammar")
    return REF


def test_parse_mcraft_cfg(reference):
    s = load_config(f"{reference}/MCraft.cfg")
    assert s.dims.n_servers == 3 and s.dims.n_values == 2
    assert s.server_names == ("r1", "r2", "r3")
    assert s.value_names == ("v1", "v2")
    assert s.invariants == ["TypeOK"]
    assert s.constraints == [] and not s.smoke
    assert s.check_deadlock            # TLC default: on
    assert s.bounds.max_term is None   # MCraft.cfg is unbounded


SMOKERAFT = os.path.join(REPO, "configs/Smokeraft.cfg")


def test_parse_smokeraft_cfg():
    """The repository's copy of upstream's smoke test (the text is this
    repo's, from SURVEY.md's account: the file says so)."""
    s = load_config(SMOKERAFT)
    assert s.dims.n_servers == 3 and s.dims.n_values == 2
    assert s.invariants == ["TypeOK"]
    assert s.bounds.max_term is None and s.bounds.max_log_len is None
    assert s.dims.max_log == 12     # no MaxLogLen: the loader's own
    assert s.smoke and s.smoke_k == 2          # Smokeraft.tla:17-19
    assert s.max_seconds == 1.0                # TLCGet("duration") > 1
    assert s.max_diameter == 100               # TLCGet("diameter") > 100
    assert not s.check_deadlock                # Smokeraft.cfg:48
    assert "StopAfter" not in s.constraints    # consumed into budgets


def test_parse_bounded_config():
    s = load_config(os.path.join(REPO, "configs/MCraft_bounded.cfg"))
    assert s.dims.n_servers == 3 and s.dims.n_values == 2
    assert (s.bounds.max_term, s.bounds.max_log_len,
            s.bounds.max_msg_count) == (3, 2, 1)
    assert s.constraints == ["BoundedSpace"]
    assert s.dims.max_log == 3     # MaxLogLen + 1 append headroom


def test_parse_raft5_config():
    s = load_config(os.path.join(REPO, "configs/raft5_bounded.cfg"))
    assert s.dims.n_servers == 5
    assert s.bounds.max_term == 4 and s.bounds.max_log_len == 4


def test_module_definition_scan():
    text = "foo == \n{a, b}\nk ==\n   2\nbar == {x}\n"
    d = scan_module_definitions(text)
    assert d == {"foo": ("a", "b"), "k": 2, "bar": ("x",)}


def test_stop_after_scan():
    from raft_tla_tpu.utils.cfg import scan_exit_operators
    text = ('StopAfter ==\n  \\/ TLCSet("exit", TLCGet("duration") > 7)\n'
            '  \\/ TLCSet("exit", TLCGet("diameter") > 42)\n')
    op = scan_exit_operators(text)["StopAfter"]
    assert op.conds == (("duration", 7.0), ("diameter", 42.0)) and op.pure


def test_unknown_constant_raises(tmp_path):
    cfgf = tmp_path / "broken.cfg"
    cfgf.write_text("CONSTANT Value = {v1}\nSPECIFICATION Spec\n")
    with pytest.raises(ValueError, match="Server"):
        load_config(str(cfgf))


def test_parse_tpu_backend_directives():
    """"\\* TPU:" comment directives select the engine backend while the
    file stays a valid stock-TLC cfg (BASELINE.json north star)."""
    s = load_config(os.path.join(REPO, "configs/TPUraft.cfg"))
    assert s.dims.n_servers == 5
    assert s.bounds.max_term == 4 and s.bounds.max_log_len == 4
    assert s.backend == {"BATCH": 8192, "QUEUE_CAPACITY": 1 << 22,
                         "SEEN_CAPACITY": 1 << 25, "N_MSG_SLOTS": 48,
                         "CHECKPOINT_INTERVAL": 300}
    assert s.dims.n_msg_slots == 48        # backend key reached dims
    # CLI flag wins over the directive.
    s2 = load_config(os.path.join(REPO, "configs/TPUraft.cfg"),
                     n_msg_slots=40)
    assert s2.dims.n_msg_slots == 40


def test_unknown_backend_key_raises(tmp_path):
    cfgf = tmp_path / "bad.cfg"
    cfgf.write_text("\\* TPU: BOGUS_KEY = 1\n"
                    "CONSTANT Server = {r1}\nCONSTANT Value = {v1}\n")
    with pytest.raises(ValueError, match="BOGUS_KEY"):
        load_config(str(cfgf))


def test_reference_cfgs_have_no_backend_keys(reference):
    assert load_config(f"{reference}/MCraft.cfg").backend == {}


def test_backend_directives_reach_engine_config():
    """API precedence: run_check/make_engine honor backend keys when no
    explicit EngineConfig is supplied (not just the CLI path)."""
    from raft_tla_tpu.engine.check import engine_config_from_backend
    s = load_config(os.path.join(REPO, "configs/TPUraft.cfg"))
    ec = engine_config_from_backend(s)
    assert ec.batch == 8192
    assert ec.queue_capacity == 1 << 22
    assert ec.seen_capacity == 1 << 25
    assert ec.checkpoint_interval_seconds == 300.0


def test_property_rejected_loudly(tmp_path):
    """A temporal PROPERTY must fail the load, mirroring ACTION_CONSTRAINT:
    silently dropping it would let the cfg 'pass' a property that was
    never checked (liveness needs a different algorithm than safety BFS)."""
    cfgf = tmp_path / "liveness.cfg"
    cfgf.write_text(
        "CONSTANTS\n    Server = {r1, r2, r3}\n    Value = {v1}\n"
        "    Follower = Follower\n    Candidate = Candidate\n"
        "    Leader = Leader\n    Nil = Nil\n"
        "    RequestVoteRequest = RequestVoteRequest\n"
        "    RequestVoteResponse = RequestVoteResponse\n"
        "    AppendEntriesRequest = AppendEntriesRequest\n"
        "    AppendEntriesResponse = AppendEntriesResponse\n"
        "SPECIFICATION Spec\nPROPERTY EventuallyLeader\n")
    with pytest.raises(NotImplementedError, match="EventuallyLeader"):
        load_config(str(cfgf))


def test_symmetry_rejected_loudly(tmp_path):
    """SYMMETRY quotients the state space — running without it would report
    non-TLC distinct-state counts with no warning (MCraft.cfg deliberately
    has none; SURVEY §1 L5), so the statement must fail the load by name."""
    cfgf = tmp_path / "sym.cfg"
    cfgf.write_text("CONSTANT Server = {r1}\nCONSTANT Value = {v1}\n"
                    "SYMMETRY Perms\n")
    with pytest.raises(NotImplementedError, match="SYMMETRY Perms"):
        load_config(str(cfgf))


def test_view_rejected_loudly(tmp_path):
    cfgf = tmp_path / "view.cfg"
    cfgf.write_text("CONSTANT Server = {r1}\nCONSTANT Value = {v1}\n"
                    "VIEW NoTermView\n")
    with pytest.raises(NotImplementedError, match="VIEW NoTermView"):
        load_config(str(cfgf))


def test_scan_exit_operators():
    """The general TLCGet/TLCSet coupling (SURVEY §5.5): any operator of the
    Smokeraft StopAfter shape is recognized, per counter; parameterized
    definitions bound operator bodies; block comments are stripped."""
    from raft_tla_tpu.utils.cfg import scan_exit_operators
    text = ('StopAfter ==\n'
            '    /\\ TLCSet("exit", TLCGet("duration") > 7)\n'
            '    /\\ TLCSet("exit", TLCGet("diameter") > 42)\n'
            'Helper(x) ==\n'
            '    TLCSet("exit", TLCGet("distinct") > 5)\n'
            'BigRun ==\n'
            '    TLCSet("exit", TLCGet("distinct") > 1000000)\n'
            'Mixed ==\n'
            '    /\\ TLCSet("exit", TLCGet("distinct") > 10)\n'
            '    /\\ x < 5\n'
            'Commented == (* TLCSet("exit", TLCGet("level") > 5) *) 3\n')
    ops = scan_exit_operators(text)
    assert ops["StopAfter"].conds == (("duration", 7.0), ("diameter", 42.0))
    assert ops["StopAfter"].pure
    # Helper(x)'s condition must NOT leak into StopAfter's body.
    assert ops["Helper"].conds == (("distinct", 5.0),)
    assert ops["BigRun"].conds == (("distinct", 1000000.0),)
    assert not ops["Mixed"].pure        # budget + predicate conjunct
    assert "Commented" not in ops       # block comment stripped


def test_unknown_exit_counter_rejected_only_when_used(tmp_path):
    """An unused operator with an unknown counter must not poison the load;
    naming it as CONSTRAINT must reject loudly."""
    cfg_path = _write_exit_model(tmp_path, "level", 10)
    with pytest.raises(NotImplementedError, match="level"):
        load_config(cfg_path)
    # Same operator, no CONSTRAINT naming it: loads fine.
    text = (tmp_path / "tiny.cfg").read_text()
    (tmp_path / "tiny.cfg").write_text(
        text.replace("CONSTRAINT StopEarly\n", ""))
    s = load_config(str(tmp_path / "tiny.cfg"))
    assert s.exit_conditions == ()


def test_mixed_budget_predicate_constraint_rejected(tmp_path):
    (tmp_path / "mix.tla").write_text(
        "---- MODULE mix ----\nEXTENDS raft\n"
        'Bounded ==\n    /\\ TLCSet("exit", TLCGet("distinct") > 10)\n'
        "    /\\ Len(log[r1]) < 5\n====\n")
    (tmp_path / "mix.cfg").write_text(
        "CONSTANTS\n    Server = {r1}\n    Value = {v1}\n"
        "SPECIFICATION Spec\nCONSTRAINT Bounded\n")
    with pytest.raises(NotImplementedError, match="Bounded"):
        load_config(str(tmp_path / "mix.cfg"))


def _write_exit_model(tmp_path, counter, threshold):
    (tmp_path / "tiny.tla").write_text(
        "---- MODULE tiny ----\nEXTENDS raft\n"
        f'StopEarly ==\n    TLCSet("exit", TLCGet("{counter}") '
        f"> {threshold})\n====\n")
    cfgf = tmp_path / "tiny.cfg"
    cfgf.write_text(
        "CONSTANTS\n    Server = {r1, r2, r3}\n    Value = {v1}\n"
        "    Follower = Follower\n    Candidate = Candidate\n"
        "    Leader = Leader\n    Nil = Nil\n"
        "    RequestVoteRequest = RequestVoteRequest\n"
        "    RequestVoteResponse = RequestVoteResponse\n"
        "    AppendEntriesRequest = AppendEntriesRequest\n"
        "    AppendEntriesResponse = AppendEntriesResponse\n"
        "SPECIFICATION Spec\nINVARIANT TypeOK\nCONSTRAINT StopEarly\n")
    return str(cfgf)


def test_distinct_budget_constraint_loads(tmp_path):
    """A cfg-defined constraint over TLCGet("distinct") needs no code
    changes: it loads as an exit condition, not a state predicate."""
    s = load_config(_write_exit_model(tmp_path, "distinct", 500))
    assert s.exit_conditions == (("distinct", 500.0),)
    assert s.constraints == []          # consumed as a budget
    assert s.max_seconds is None and s.max_diameter is None


def test_smokeraft_stopafter_still_routes_to_native_budgets():
    s = load_config(SMOKERAFT)
    assert s.max_seconds == 1.0 and s.max_diameter == 100
    assert s.exit_conditions == ()


def test_progress_seconds_backend_directive(tmp_path):
    """PROGRESS_SECONDS rides the same flag > directive > default chain as
    every other backend key."""
    cfgf = tmp_path / "p.cfg"
    cfgf.write_text("\\* TPU: PROGRESS_SECONDS = 300\n"
                    "CONSTANT Server = {r1}\nCONSTANT Value = {v1}\n")
    s = load_config(str(cfgf))
    assert s.backend["PROGRESS_SECONDS"] == 300


# -- the pipelines that were deleted (PR 31): rejected by name -------------

VALID = r"auto/v1/v2"


@pytest.mark.parametrize("dead", ["v3", "v4"])
def test_directive_for_a_deleted_pipeline_is_rejected(dead, tmp_path):
    """``\\* TPU: PIPELINE = v4`` fails at load with the three valid
    values named; it is not mapped to v2."""
    cfgf = tmp_path / "dead.cfg"
    cfgf.write_text(f"\\* TPU: PIPELINE = {dead}\n"
                    "CONSTANT Server = {r1}\nCONSTANT Value = {v1}\n")
    with pytest.raises(ValueError, match=f"{VALID}.*{dead}"):
        load_config(str(cfgf))
    cfgf.write_text("\\* TPU: PIPELINE = v2\n"
                    "CONSTANT Server = {r1}\nCONSTANT Value = {v1}\n")
    assert load_config(str(cfgf)).backend == {"PIPELINE": "v2"}


@pytest.mark.parametrize("dead", ["v3", "v4"])
def test_cli_flag_for_a_deleted_pipeline_is_rejected(dead, capsys):
    from raft_tla_tpu.cli import main
    with pytest.raises(SystemExit) as exc:
        main(["check", os.path.join(REPO, "configs/MCraft_bounded.cfg"),
              "--pipeline", dead])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert f"invalid choice: '{dead}'" in err
    assert "auto, v1, v2" in err


# -- the modelled-performance options (PR 46): refused by name ------------

@pytest.mark.parametrize("dead", ["PERF", "PROFILE_CHUNKS"])
def test_directive_for_a_deleted_option_is_rejected(dead, tmp_path):
    cfgf = tmp_path / "dead.cfg"
    cfgf.write_text(f"\\* TPU: {dead} = 1\n"
                    "CONSTANT Server = {r1}\nCONSTANT Value = {v1}\n")
    with pytest.raises(ValueError,
                       match=f"unknown TPU backend key '{dead}'"):
        load_config(str(cfgf))


@pytest.mark.parametrize("dead", ["--perf", "--profile-chunks"])
def test_cli_flag_for_a_deleted_option_is_rejected(dead, capsys):
    from raft_tla_tpu.cli import main
    with pytest.raises(SystemExit) as exc:
        main(["check", os.path.join(REPO, "configs/MCraft_bounded.cfg"),
              dead])
    assert exc.value.code == 2
    assert f"unrecognized arguments: {dead}" in capsys.readouterr().err


# -- README.md names only what exists --------------------------------------

def _readme():
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        return f.read()


def test_readme_names_only_flags_and_directives_that_exist():
    """Every ``--flag`` the README names is an option of some entry point
    (the CLI, the server, bench.py, the smoke, the benchmark, a script;
    XLA's own ``--xla_*`` flags aside), and every ``\\* TPU:`` directive is
    in ``utils/cfg.py``'s set.  The README has described removed options
    before."""
    import glob
    import re
    from raft_tla_tpu.utils.cfg import _BACKEND_KEYS
    text = _readme()
    sources = [os.path.join(REPO, p) for p in (
        "raft_tla_tpu/cli.py", "raft_tla_tpu/server.py", "bench.py",
        "chip_smoke.py", "benchmark/run.py")]
    sources += glob.glob(os.path.join(REPO, "scripts", "*.py"))
    known = set()
    for path in sources:
        with open(path, encoding="utf-8") as f:
            known |= set(re.findall(r'"(--[a-z][a-z0-9-]+)"', f.read()))
    named = set(re.findall(r"(?<![\w-])(--[a-z][a-z0-9_-]+)", text))
    named = {flag for flag in named if not flag.startswith("--xla_")}
    assert len(named) > 40 and not named - known, sorted(named - known)
    directives = set(re.findall(r"TPU:\s*([A-Z_]+)", text))
    assert len(directives) > 5 and not directives - _BACKEND_KEYS, \
        sorted(directives - _BACKEND_KEYS)


def test_readme_names_only_engine_config_fields_that_exist():
    import dataclasses
    import re
    from raft_tla_tpu.engine.bfs import EngineConfig, EngineResult
    text = _readme()
    for cls in (EngineConfig, EngineResult):
        fields = {f.name for f in dataclasses.fields(cls)}
        named = set(re.findall(cls.__name__ + r"\s*[.(]\s*(\w+)", text))
        assert named and not named - fields, sorted(named - fields)

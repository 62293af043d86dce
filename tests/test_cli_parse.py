"""A CLI call builds the top-level parser and only the sub-parser its
command names (`__main__.COMMANDS`); no argv, `-h` or an unknown command
builds every one.

Four guards:
  * the one sub-parser and the full build give equal Namespaces, for
    every command and every `fleet` verb, the benchmark's own argvs
    among them, and print the same `<cmd> -h`;
  * the top-level help and errors a user sees, text and exit code, are
    the full build's;
  * the engagement count: `cli.parser_one` once a call and `built` 1 on
    `cli_parse` for the benchmark's explore, `cli.parser_full` and
    every command for an unknown one;
  * no parser outlives a call: a default read from the environment
    follows the variable from one `main` call to the next.
"""

import argparse
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import madsim_tpu.__main__ as cli  # noqa: E402
from benchmark import cells  # noqa: E402
from madsim_tpu.perf.recorder import PerfRecorder  # noqa: E402

SEED = 3_000_000_000
CORPUS = "/nonexistent/corpus.json"


def cell_argv(workload: str) -> list:
    """The argv a campaign of `workload` hands the CLI."""
    cell = cells.load_cell(workload)
    extra = () if cell.kind == "sweep" else (CORPUS,)
    return list(cells.load_campaign(cell).argv(cell, SEED, *extra))


#: one argv a command, with the flags it requires; `fleet` once a verb
ARGVS = {
    "explore": cell_argv("raft5_sweep"),
    "explore_x4": cell_argv("raft5_sweep_x4"),
    "hunt": cell_argv("etcd_mvcc4_hunt"),
    "regress": ["regress", "--corpus", CORPUS],
    "audit": ["audit", "--corpus", CORPUS],
    "replay": ["replay", "--machine", "kv", "--seed", "7", "--tail", "0"],
    "trace": ["trace", "--seed", "3", "--perfetto", "t.json"],
    "shrink": ["shrink", "--seed", "5", "--faults", "3"],
    "why": ["why", "12", "--provenance", "--json", "w.json"],
    "check": ["check", "--machine", "kv", "--seeds", "8"],
    "perf": ["perf", "out.json", "--seeds", "64"],
    "prof": ["prof", "compile", "--batch", "64"],
    "coverage": ["coverage", "doc.json", "--diff", "old.json"],
    "serve": ["serve", "--service", "stats", "--addr", "127.0.0.1:0"],
    "lint": ["lint", "madsim_tpu", "--rules", "D", "--json"],
    "fleet serve": ["fleet", "serve", "--addr", "127.0.0.1:0"],
    "fleet worker": ["fleet", "worker", "--drain", "--driver", "synthetic"],
    "fleet submit": ["fleet", "submit", "--machine", "echo", "--seeds", "64"],
    "fleet status": ["fleet", "status", "J", "--wait", "1"],
    "fleet result": ["fleet", "result", "J"],
    "fleet cancel": ["fleet", "cancel", "J", "--no-retry"],
    "fleet queue": ["fleet", "queue"],
    "fleet watch": ["fleet", "watch", "J", "--since", "3"],
    "fleet timeline": ["fleet", "timeline", "J"],
    "fleet profile": ["fleet", "profile", "J", "--out", "p.json"],
    "fleet top": ["fleet", "top", "--once"],
    "fleet fsck": ["fleet", "fsck", "--dry-run"],
    "fleet chaos": ["fleet", "chaos", "--seed", "3", "--profile", "claims"],
}
#: what a user sees from the top-level parser: only the full build says it
TOP_LEVEL = {
    "unrecognized flag": ["explore", "--bogus"],
    "unrecognized argument": ["regress", "--corpus", "c.json", "extra"],
    "unknown command": ["nope"],
    "misspelt command": ["explor", "--seeds", "8"],
    "no argv": [],
    "help": ["--help"],
    "short help": ["-h"],
}
#: errors a sub-parser raises with its own usage
SUB_LEVEL = {
    "missing fleet verb": ["fleet"],
    "missing positional": ["coverage"],
    "bad choice": ["explore", "--rng-stream", "9"],
}


def full() -> argparse.ArgumentParser:
    return cli._build_parser(list(cli.COMMANDS))


def subcommands(parser) -> dict:
    """Name -> sub-parser, of the sub-parsers `parser` built."""
    [action] = [a for a in parser._actions
                if isinstance(a, argparse._SubParsersAction)]
    return action.choices


def outcome(parse, argv, capsys):
    """(exit code, stdout, stderr) of a parse that exits."""
    with pytest.raises(SystemExit) as exit_:
        parse(list(argv))
    out = capsys.readouterr()
    return exit_.value.code, out.out, out.err


def test_every_command_and_fleet_verb_has_a_case():
    fleet = subcommands(full())["fleet"]
    assert {a[0] for a in ARGVS.values()} == set(cli.COMMANDS)
    assert {a[1] for a in ARGVS.values() if a[0] == "fleet"} \
        == set(subcommands(fleet))
    assert list(cli.COMMANDS)[:2] == ["explore", "replay"]


@pytest.mark.parametrize("case", ARGVS)
def test_one_sub_parser_parses_as_the_full_build(case):
    argv = ARGVS[case]
    one = cli._build_parser([argv[0]])
    assert list(subcommands(one)) == [argv[0]]
    assert vars(one.parse_args(argv)) == vars(full().parse_args(argv))


@pytest.mark.parametrize("case", ARGVS)
def test_a_commands_help_is_the_full_builds(case, capsys, monkeypatch):
    monkeypatch.setenv("COLUMNS", "100")
    argv = ARGVS[case][:2 if case.startswith("fleet") else 1] + ["-h"]
    got = outcome(cli._parse, argv, capsys)
    assert got == outcome(full().parse_args, argv, capsys)
    assert got[0] == 0
    assert got[1].startswith("usage: madsim_tpu " + " ".join(argv[:-1]) + " [")


@pytest.mark.parametrize("case", {**TOP_LEVEL, **SUB_LEVEL})
def test_help_and_error_text_is_the_full_builds(case, capsys, monkeypatch):
    """The top-level usage names every command in table order, whichever
    sub-parsers were built."""
    monkeypatch.setenv("COLUMNS", "100")
    argv = {**TOP_LEVEL, **SUB_LEVEL}[case]
    got = outcome(cli._parse, argv, capsys)
    assert got == outcome(full().parse_args, argv, capsys)
    assert got[0] == (0 if "help" in case else 2)
    top = "usage: madsim_tpu [-h]" in got[1] + got[2]
    assert top == (case in TOP_LEVEL)
    assert ("{" + ",".join(cli.COMMANDS) + "}" in got[1] + got[2]) == top


def test_a_benchmark_call_builds_one_sub_parser(monkeypatch):
    def build(args):
        raise SystemExit(0)  # parsed: nothing to run here

    monkeypatch.setattr(cli, "_build_engine", build)
    with PerfRecorder() as rec:
        with pytest.raises(SystemExit):
            cli.main(ARGVS["explore"])
    [span] = [s for s in rec.spans if s["name"] == "cli_parse"]
    assert span["args"] == {"built": 1}
    assert rec.counters.get("cli.parser_one") == 1
    assert "cli.parser_full" not in rec.counters


@pytest.mark.parametrize("case", ["explore", "explore_x4", "hunt", "regress",
                                  "audit"])
def test_each_call_the_benchmark_drives_builds_one_sub_parser(
        case, monkeypatch, tmp_path):
    """Through the benchmark's own in-process driver, with its annotating
    recorder: one `cli.parser_one` and `built` 1 on the call's
    `cli_parse`, for a sweep's campaign and each call of a hunt."""
    from benchmark import drive

    def build(args):
        raise SystemExit(0)  # parsed: nothing to run here

    monkeypatch.setattr(cli, "_build_engine", build)
    argv = [str(tmp_path / "c.json") if a == CORPUS else a
            for a in ARGVS[case]]
    session = drive.Session(str(tmp_path), traced=True)
    with session.recording():
        try:
            session.cli(argv)
        except SystemExit:
            pass
    rec = session.recorder
    [span] = [s for s in rec.spans if s["name"] == "cli_parse"]
    assert span["args"] == {"built": 1}
    assert rec.counters.get("cli.parser_one") == 1
    assert "cli.parser_full" not in rec.counters


def test_an_unknown_command_builds_every_sub_parser(capsys):
    with PerfRecorder() as rec:
        with pytest.raises(SystemExit):
            cli.main(["nope"])
    capsys.readouterr()
    [span] = [s for s in rec.spans if s["name"] == "cli_parse"]
    assert span["args"] == {"built": len(cli.COMMANDS)} == {"built": 15}
    assert rec.counters.get("cli.parser_full") == 1
    assert "cli.parser_one" not in rec.counters


@pytest.mark.parametrize("var, argv, dest", [
    ("MADSIM_TPU_FLEET_ROOT", ["fleet", "fsck"], "root"),
    ("MADSIM_TPU_COMPILE_CACHE", ["fleet", "worker", "--driver", "synthetic"],
     "compile_cache"),
])
def test_an_environment_default_is_read_at_every_call(var, argv, dest,
                                                      monkeypatch, tmp_path):
    seen = []
    monkeypatch.setattr(cli, "cmd_fleet",
                        lambda args: seen.append(getattr(args, dest)) or 0)
    for name in ("a", "b"):
        monkeypatch.setenv(var, str(tmp_path / name))
        assert cli.main(list(argv)) == 0
    assert seen == [str(tmp_path / "a"), str(tmp_path / "b")]

"""Subcommand routing for `python -m automerge_tpu.perf` (perf/__main__.py):
every registered subcommand reaches its module entry with the remaining
argv, unknown commands exit nonzero with a usage line, and the bare/help
invocations print the command list."""

import pytest

import automerge_tpu.perf.__main__ as perf_main


def _capture(monkeypatch, module, attr, rc=0):
    """Replace `module.attr` with a recorder returning `rc`."""
    calls = []

    def fake(argv=None):
        calls.append(list(argv) if argv is not None else None)
        return rc
    monkeypatch.setattr(module, attr, fake)
    return calls


@pytest.mark.parametrize("cmd,modname,attr", [
    ("doctor", "doctor", "main"),
    ("explain", "explain", "main"),
    ("top", "top", "main"),
    ("dispatch", "dispatchplane", "main"),
    ("tenant", "tenantplane", "main"),
    ("remediate", "remediate", "smoke_main"),
    ("move", "moveplane", "smoke_main"),
    ("bootstrap", "bootstrap", "smoke_main"),
])
def test_lazy_subcommands_route_with_rest_argv(monkeypatch, cmd, modname,
                                               attr):
    import importlib
    mod = importlib.import_module(f"automerge_tpu.perf.{modname}")
    calls = _capture(monkeypatch, mod, attr, rc=0)
    rc = perf_main.main([cmd, "--flag", "v"])
    assert rc == 0
    assert calls == [["--flag", "v"]]


@pytest.mark.parametrize("cmd,attr", [
    ("check", "_cmd_check"),
    ("report", "_cmd_report"),
    ("contention", "_cmd_contention"),
])
def test_builtin_subcommands_route(monkeypatch, cmd, attr):
    calls = _capture(monkeypatch, perf_main, attr, rc=0)
    assert perf_main.main([cmd, "--x"]) == 0
    assert calls == [["--x"]]


def test_subcommand_exit_code_propagates(monkeypatch):
    from automerge_tpu.perf import doctor
    _capture(monkeypatch, doctor, "main", rc=3)
    assert perf_main.main(["doctor"]) == 3


def test_unknown_command_exits_nonzero_with_usage(capsys):
    rc = perf_main.main(["frobnicate"])
    assert rc == 2
    err = capsys.readouterr().err
    assert "unknown command 'frobnicate'" in err
    for cmd in ("report", "check", "contention", "doctor", "explain",
                "top", "dispatch", "tenant", "remediate", "move",
                "bootstrap"):
        assert cmd in err


def test_bare_and_help_print_command_list(capsys):
    assert perf_main.main([]) == 2
    assert perf_main.main(["--help"]) == 0
    out = capsys.readouterr().out
    assert "dispatch" in out and "doctor" in out

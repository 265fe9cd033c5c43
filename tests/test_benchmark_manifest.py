"""Every string of the benchmark's index, held to the driver's rule.

`BENCHMARK.json` is refused before any run where a `name` is not 1 to 64
of letters, digits, `_`, `.`, `-` (starting with none of `.` `-`), or a
`source`, a `why` or a `layer` is not one line of 1 to 200 printable ASCII
characters: a configuration's strings too, which the benchmark's own test
does not read (PR 36 was refused for one). Each configuration and cell the
index lists has to agree with its file under benchmarks/. One case an
entry.
"""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[\x20-\x7e]{1,200}$")     # printable ASCII, one line

with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as _f:
    BENCH = json.load(_f)
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}}
ENTRIES = [(kind, e) for kind in KEYS for e in BENCH[kind]]


def _file(path: str) -> dict:
    with open(os.path.join(REPO, path), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("kind,entry", ENTRIES, ids=[
    f"{kind}:{e.get('name')}" for kind, e in ENTRIES])
def test_an_entry_keeps_the_drivers_rule(kind, entry):
    assert set(entry) <= KEYS[kind] and "name" in entry
    assert NAME.match(entry["name"]), entry["name"]
    for key in ("source", "why", "layer"):
        if key in entry and (kind, key) != ("end_to_end", "source") \
                and (kind, key) != ("per_layer", "source"):
            assert LINE.match(entry[key]), (key, len(entry[key]))
    if kind == "configs":
        assert 0 <= len(entry["reduced"]) <= 16
        assert all(NAME.match(k) for k in entry["reduced"])
        assert entry["file"] == f"benchmarks/configs/{entry['name']}.json"
        data = _file(entry["file"])
        assert data["name"] == entry["name"]
        assert data["source"] == entry["source"]
        assert data["reduced"] == entry["reduced"]
        assert set(entry["reduced"]) <= set(data["reduced_how"])
        assert any(w["config"] == entry["name"] for w in BENCH["workloads"])
    elif kind == "workloads":
        assert NAME.match(entry["config"]) and NAME.match(entry["traffic"])
        assert entry["chips"] in (1, 4)
        assert entry == _file(f"benchmarks/workloads/{entry['name']}.json")
        assert entry["config"] in {c["name"] for c in BENCH["configs"]}
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "traffic", entry["traffic"] + ".json"))
    else:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
        cells = {w["name"] for w in BENCH["workloads"]}
        assert set(entry.get("workloads", ())) <= cells
        if kind == "per_layer":
            assert entry["workloads"]
            assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}


def test_no_two_entries_share_a_name_and_the_file_is_small():
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[kind]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["per_layer"]) <= 128
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    for word in BENCH["command"]:
        assert LINE.match(word)

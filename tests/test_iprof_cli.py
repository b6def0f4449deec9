"""iprof CLI (§3.4 Fig 4) end-to-end: run → tally/pretty/timeline/validate
→ multi-rank combine."""

import json
import os

import numpy as np
import pytest

from repro.core.aggregate import save_tally
from repro.core.iprof import main as iprof
from repro.core.plugins.tally import tally_trace


def _traced_workload(tmp_path, rank=0, aggregate_only=False, columnar=False):
    """Run a tiny traced workload via the iprof 'run' subcommand."""
    out = str(tmp_path / f"trace_r{rank}")
    args = ["run", "-m", "default", "-o", out, "--rank", str(rank)]
    if aggregate_only:
        args.append("--aggregate-only")
    if columnar:
        args.append("--columnar")
    args.append("tests.iprof_target:main")
    rc = iprof(args)
    assert rc == 0
    return out


def test_run_and_tally(tmp_path, capsys):
    out = _traced_workload(tmp_path)
    capsys.readouterr()
    assert iprof(["tally", out]) == 0
    text = capsys.readouterr().out
    assert "train_step" in text and "Time(%)" in text


def test_tally_jobs_matches_serial(tmp_path, capsys):
    """--jobs N renders the identical table (sharded fold, same tally)."""
    out = _traced_workload(tmp_path)
    capsys.readouterr()
    assert iprof(["tally", out]) == 0
    serial = capsys.readouterr().out
    assert iprof(["tally", out, "--jobs", "3"]) == 0
    assert capsys.readouterr().out == serial
    assert iprof(["tally", out, "--jobs", "3", "--no-sidecar"]) == 0
    assert capsys.readouterr().out == serial


def test_tally_empty_trace_dir_warns(tmp_path, capsys):
    """Zero completed streams (metadata only): warn on stderr, exit 0 with
    an empty table — not a crash, not silence."""
    import shutil

    out = _traced_workload(tmp_path)
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    shutil.copy(os.path.join(out, "metadata.json"), empty)
    capsys.readouterr()
    assert iprof(["tally", empty]) == 0
    cap = capsys.readouterr()
    assert "no completed streams" in cap.err
    assert "0 Processes" in cap.out


def test_index_then_tally_uses_sidecars(tmp_path, capsys):
    """iprof index builds .ctfcol sidecars; tally output is unchanged."""
    from repro.core.ctf import load_sidecar, stream_files

    out = _traced_workload(tmp_path)
    capsys.readouterr()
    assert iprof(["tally", out]) == 0
    before = capsys.readouterr().out
    assert iprof(["index", out]) == 0
    assert "indexed" in capsys.readouterr().out
    for p in stream_files(out):
        assert load_sidecar(p) is not None
    assert iprof(["tally", out]) == 0
    assert capsys.readouterr().out == before


def test_run_columnar_writes_sidecars(tmp_path, capsys):
    """iprof run --columnar leaves valid sidecars next to the streams."""
    from repro.core.ctf import load_sidecar, stream_files

    out = _traced_workload(tmp_path, columnar=True)
    paths = stream_files(out)
    assert paths
    for p in paths:
        assert load_sidecar(p) is not None


def test_pretty(tmp_path, capsys):
    out = _traced_workload(tmp_path)
    capsys.readouterr()
    assert iprof(["pretty", out, "-n", "5"]) == 0
    assert "vpid" in capsys.readouterr().out


def test_timeline(tmp_path, capsys):
    out = _traced_workload(tmp_path)
    tl = str(tmp_path / "tl.json")
    assert iprof(["timeline", out, "-o", tl]) == 0
    doc = json.load(open(tl))
    assert len(doc["traceEvents"]) > 0


def test_validate(tmp_path, capsys):
    out = _traced_workload(tmp_path)
    assert iprof(["validate", out]) == 0


def test_combine_ranks(tmp_path, capsys):
    """§3.7: aggregate-only rank traces → global master composite."""
    for r in range(4):
        _traced_workload(tmp_path / f"r{r}", rank=r, aggregate_only=True)
    capsys.readouterr()
    assert iprof(["combine", str(tmp_path)]) == 0
    text = capsys.readouterr().out
    assert "train_step" in text
    # composite counts = 4 ranks × 3 steps
    import re

    m = re.search(r"train_step.*?\|\s+(\d+)\s+\|", text)
    assert m and int(m.group(1)) == 12


@pytest.mark.parametrize("entry", ["returns_2", "exits_2"])
def test_run_propagates_target_exit_code(tmp_path, entry):
    """A target that returns 2, or raises SystemExit(2), makes the run exit 2
    — with its trace still written."""
    out = str(tmp_path / "t")
    assert iprof(["run", "-o", out, f"tests.iprof_target:{entry}"]) == 2
    assert ("ust_repro", "train_step") in tally_trace(out).apis


@pytest.mark.parametrize(
    "entry,argv,rows",
    [
        (
            "repro.launch.serve:main",
            ["--requests", "3", "--prompt-len", "8", "16", "--new-tokens", "3"],
            ("prefill", "decode_step", "dispatch", "block_until_ready"),
        ),
        (
            "repro.launch.train:main",
            ["--steps", "2", "--seq", "16", "--batch", "2"],
            ("train_step", "data_next", "dispatch", "block_until_ready"),
        ),
    ],
)
def test_launchers_under_iprof_run(tmp_path, entry, argv, rows):
    """The entry points a user traces run under iprof and tally their spans;
    the report names the device and the kernel path."""
    out, report = tmp_path / "t", tmp_path / "report.json"
    rc = iprof(
        ["run", "-o", str(out), entry, "--", "--arch", "mamba2-1.3b", "--smoke", *argv,
         "--report", str(report)]
    )
    assert rc == 0
    t = tally_trace(str(out))
    names = {api for _, api in t.apis}
    assert set(rows) <= names and t.discarded == 0
    rep = json.loads(report.read_text())
    assert rep["device"]["platform"] == "cpu" and rep["kernels"] == "ref"
    meta = json.loads((out / "metadata.json").read_text())
    assert meta["env"]["device"] == rep["device"] and meta["env"]["kernels"] == "ref"

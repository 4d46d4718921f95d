"""`BENCHMARK.json` against the benchmark contract, and every file it
names resolved by name (CPU only, no JAX)."""

from __future__ import annotations

import hashlib
import json
import os
import re
import shutil

import pytest

from perfbench import manifest

ROOT = manifest.ROOT
BENCH = manifest.Benchmark()
SPEC = BENCH.spec

E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = E2E_SOURCES | {"program_span", "program_counter"}
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
WIDTH_RE = re.compile(r"(_dim|_rank|hidden|intermediate|latent|state_size|"
                      r"head|expansion|experts_per_tok)")


def _one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51


def test_paths_and_command():
    paths = SPEC["paths"]
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH_RE.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = SPEC["command"]
    assert 1 <= len(cmd) <= 32 and all(_one_line(w) for w in cmd)
    for w in cmd:
        assert not w.startswith("/") and ".." not in w.split("/")
        if os.path.exists(os.path.join(ROOT, w)):
            assert any(w == p or w.startswith(p + "/") for p in paths), w


def test_names_units_and_uniqueness():
    groups = [SPEC["configs"], SPEC["workloads"],
              SPEC["end_to_end"] + SPEC["per_layer"]]
    for group in groups:
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        for n in names:
            assert manifest.NAME_RE.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert manifest.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert manifest.NAME_RE.match(w["config"])
        assert manifest.NAME_RE.match(w["traffic"])


def test_configs_resolve():
    files = [c["file"] for c in SPEC["configs"]]
    assert 1 <= len(SPEC["configs"]) <= 24
    assert len(files) == len(set(files))
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert _one_line(c["source"]) and _one_line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        cfg = BENCH.config(c["name"])
        assert cfg["name"] == c["name"]
        gen, ref = manifest.family(cfg)
        assert callable(gen.generate) and callable(gen.build)
        assert callable(ref.check)
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert manifest.NAME_RE.match(key)
            assert not WIDTH_RE.search(key), key
            # every cut is stated in the file with its reason
            assert key in cfg.get("reduced", {}), key
        assert cfg["dtype"] in ("int32", "int64")


def test_workloads_resolve():
    cells = SPEC["workloads"]
    assert 1 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert _one_line(w["why"])
        BENCH.config(w["config"])
        t = BENCH.traffic(w["traffic"])
        assert t["mode"] in ("prove", "anytime")
        assert t["replay"] >= 1
        assert all(isinstance(v, (int, float)) for v in t["limits"].values())


def test_end_to_end_metrics():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert 1 <= len(e2e) <= 16
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in E2E_SOURCES
        assert 0.01 <= m["bound"] <= 0.25
    for w in SPEC["workloads"]:
        names = {m["name"] for m in BENCH.end_to_end(w["name"])}
        assert "setup_s" in names and len(names) >= 2
        # the traffic measures the one other metric the cell reports
        assert names - {"setup_s"} == {BENCH.traffic(w["traffic"])["metric"]}


def test_per_layer_metrics():
    assert 1 <= len(SPEC["per_layer"]) <= 128
    cells = {w["name"] for w in SPEC["workloads"]}
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
        assert _one_line(m["layer"])
        assert callable(BENCH.metric_reader(m["name"]))
        for cell in m.get("workloads", []):
            assert cell in cells
            reported = {e["name"] for e in BENCH.end_to_end(cell)}
            assert m["moves"] in reported, (m["name"], cell)
        layers.setdefault(m["layer"], set()).add(m["name"])
        if m["unit"] == "%" and m["name"].endswith("_roofline"):
            assert m["better"] == "higher"
    for w in cells:
        assert BENCH.per_layer(w), w
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"layer {layer!r} missing from PERF.md"


def _digest(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            if f.endswith(".pyc"):
                continue
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_is_added_by_files_alone(tmp_path):
    """A later change adds a configuration, a traffic mix, a per-layer
    metric and a cell as new files plus new entries, and every file that
    was there stays byte for byte as it was."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "perfbench"), root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    before = _digest(root / "perfbench")

    cfg = json.loads((root / "perfbench/configs/rcpsp-j30.json").read_text())
    cfg["name"] = "rcpsp-j30-tight"
    for point in cfg["grid"]:
        point["rs"] = 0.2 if point["rs"] == 0.7 else 0.5
    (root / "perfbench/configs/rcpsp-j30-tight.json").write_text(
        json.dumps(cfg))
    traffic = json.loads(
        (root / "perfbench/traffic/anytime-closed.json").read_text())
    traffic["replay"] = 2
    (root / "perfbench/traffic/anytime-two.json").write_text(
        json.dumps(traffic))
    (root / "perfbench/metrics/answers.anytime.py").write_text(
        "def read(run):\n    return float(len(run.answers))\n")

    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(
        name="rcpsp-j30-tight", source="PSPLIB j30",
        file="perfbench/configs/rcpsp-j30-tight.json",
        reduced=["grid"], why="contended resources"))
    spec["workloads"].append(dict(
        name="j30-anytime", config="rcpsp-j30-tight", traffic="anytime-two",
        chips=1, why="budgeted search under binding resources"))
    for m in spec["end_to_end"]:
        if m["name"] == "anytime_s":
            m["workloads"].append("j30-anytime")
    spec["per_layer"].append(dict(
        name="answers.anytime", unit="1", better="higher",
        source="host_clock", layer="device", moves="anytime_s",
        workloads=["j30-anytime"]))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    bench = manifest.Benchmark(str(root))
    cell = bench.cell("j30-anytime")
    assert {p["rs"] for p in bench.config(cell["config"])["grid"]} \
        == {0.2, 0.5}
    assert bench.traffic(cell["traffic"])["replay"] == 2
    assert [m["name"] for m in bench.per_layer("j30-anytime")] \
        == ["answers.anytime"]
    assert {m["name"] for m in bench.end_to_end("j30-anytime")} \
        == {"anytime_s", "setup_s"}

    class FakeRun:
        answers = [1, 2, 3]
    assert bench.metric_reader("answers.anytime")(FakeRun()) == 3.0
    after = _digest(root / "perfbench")
    assert {k: v for k, v in after.items() if k in before} == before


def test_unknown_names_raise():
    with pytest.raises(manifest.ManifestError):
        BENCH.cell("no-such-cell")
    with pytest.raises(manifest.ManifestError):
        BENCH.config("no-such-config")
    with pytest.raises(manifest.ManifestError):
        BENCH.metric_reader("no-such-metric")

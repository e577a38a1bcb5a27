"""The benchmark is driven by data: every configuration, cell and per-layer
metric is a file of its own, found by its name in ``BENCHMARK.json``, and a
new cell needs new files and entries only."""
import json
import os
import re
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def one_line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_every_file_loads_by_name():
    s = spec()
    for c in s["configs"]:
        with open(os.path.join(ROOT, c["file"])) as fh:
            conf = json.load(fh)
        assert conf["name"] == c["name"]
        for key in ("n_sites", "chi", "d", "storage_dtype", "gemm_dtype",
                    "env_dtype", "segment_len", "pad_multiple"):
            assert key in conf, (c["name"], key)
        assert 0 < conf["limits"]["widest_gap"] < 1
        assert set(c["reduced"]) == set(conf["reduced"])
    for w in s["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.traffic["samples_per_batch"] > 0
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in s["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


def test_names_units_and_lines_are_within_the_allowed_characters():
    s = spec()
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in s[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append(e["name"])
    assert len(names) == len(set(names))
    for w in s["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert one_line(w["why"]) and w["chips"] in (1, 4)
    for c in s["configs"]:
        assert one_line(c["source"]) and one_line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for m in s["end_to_end"] + s["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in s["per_layer"]:
        assert one_line(m["layer"])
    pairs = [(w["config"], w["traffic"]) for w in s["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_each_layer_metric_moves_one_metric_its_cells_report():
    s = spec()
    e2e = {m["name"]: m for m in s["end_to_end"]}
    cells = {w["name"] for w in s["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25

    def reports(cell, metric):
        return cell in e2e[metric].get("workloads", cells)
    for m in s["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(cell, m["moves"]), (m, cell)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        assert any(reports(cell, n) for n in e2e if n != "setup_s")


def test_contract_shape_and_time_budget():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert s["command"][1] == "bench/run.py"
    for p in s["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    four = sum(w["chips"] == 4 for w in s["workloads"])
    assert four <= max(1, len(s["workloads"]) // 2)
    runs = 2 + 14 * 24
    assert runs * (s["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_a_cell_dropped_into_a_copy_runs_with_no_code_change(tmp_path):
    """New configuration, traffic and workload entry in a copy of the
    benchmark: found by name, set up, measured and checked on the CPU."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    s = spec()
    with open(os.path.join(ROOT, s["configs"][0]["file"])) as fh:
        conf = json.load(fh)
    conf.update(name="dropin", chi=120, pad_multiple=128)
    conf["limits"] = {"widest_gap": 5e-5}
    (tmp_path / "bench/configs/dropin.json").write_text(json.dumps(conf))
    (tmp_path / "bench/traffic/dropin-n256.json").write_text(json.dumps({
        "samples_per_batch": 256, "mesh": None, "max_batches": 100,
        "rows_checked_per_batch": 256}))
    s["configs"].append({"name": "dropin", "source": "test",
                         "file": "bench/configs/dropin.json", "reduced": [],
                         "why": "test"})
    s["workloads"].append({"name": "dropin-n256", "config": "dropin",
                           "traffic": "dropin-n256", "chips": 1,
                           "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))

    cell = harness.load_cell("dropin-n256", str(tmp_path))
    assert cell.config["chi"] == 120 and cell.traffic["samples_per_batch"] == 256
    assert {m["name"] for m in cell.per_layer} >= {"walk_mfu",
                                                   "device_idle_share"}
    out = harness.run_cell(cell, 2 ** 33 + 5, 0.2, False,
                           t_start=time.time())
    assert out["correct"] and out["attempted"] >= 1
    assert set(out["metrics"]) == {"site_samples_per_s", "setup_s"}
    assert list(out)[-1] == "checks"
    assert not os.path.exists(tmp_path / ".bench_run")


def test_run_refuses_without_the_program(tmp_path):
    """A directory with only the benchmark's files prints no result."""
    import subprocess
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    name = spec()["workloads"][0]["name"]
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", name,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0 and r.stdout.strip() == ""


@pytest.mark.parametrize("bad", ["no-such-cell"])
def test_unknown_cell_is_an_error(bad):
    with pytest.raises(KeyError):
        harness.load_cell(bad)


def test_run_refuses_without_a_chip():
    """On the CPU the command exits non-zero and prints no result."""
    import subprocess
    name = spec()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/run.py", "--workload", name,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "TPU" in r.stderr


def test_every_file_under_the_benchmark_parses():
    """Files staged for cells not yet in BENCHMARK.json (the four-chip
    cell's configuration, traffic and metric readers) stay loadable."""
    d = os.path.join(ROOT, "bench")
    for f in os.listdir(os.path.join(d, "configs")):
        with open(os.path.join(d, "configs", f)) as fh:
            conf = json.load(fh)
        assert conf["name"] + ".json" == f
        assert conf["chi"] == conf["published"]["chi"]
        assert conf["d"] == conf["published"]["d"]
    for f in os.listdir(os.path.join(d, "traffic")):
        with open(os.path.join(d, "traffic", f)) as fh:
            assert json.load(fh)["samples_per_batch"] > 0
    for f in os.listdir(os.path.join(d, "metrics")):
        if f.endswith(".py"):
            assert callable(harness.load_reader(f[:-3]))


def test_a_configuration_without_a_limit_is_refused(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    s = spec()
    s["configs"].append({"name": "jiuzhang2", "source": "test",
                         "file": "bench/configs/jiuzhang2.json",
                         "reduced": [], "why": "test"})
    s["workloads"].append({"name": "j2-tp4-n16k", "config": "jiuzhang2",
                           "traffic": "tp4-n16k", "chips": 4, "why": "t"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    with pytest.raises(ValueError, match="no limit"):
        harness.load_cell("j2-tp4-n16k", str(tmp_path))

"""The harness finds every piece by its name, so a later change adds
files and entries, never edits one; and the command measures nothing
without a chip."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench.lib.harness import Harness  # noqa: E402
from bench.tests.tiny import make_tree  # noqa: E402

NEW_METRIC = '''"""A metric added as a file: requests due in the window."""


def read(run):
    return float(len(run.due_in_window()))
'''


def test_spec_names_resolve_to_files():
    h = Harness()
    for w in h.spec["workloads"]:
        conf = h.config(w["config"])
        mix = h.traffic(w["traffic"])
        assert h.generator(mix).generate
        assert h.reference(conf).gaps
        assert conf["serving"]["chips"] == w["chips"]
    for m in h.spec["end_to_end"] + h.spec["per_layer"]:
        assert callable(h.reader(m["name"]).read)


def test_new_config_traffic_and_metric_are_found_as_added_files(tmp_path):
    root = make_tree(tmp_path)
    bench = root / "bench"
    conf = json.loads((bench / "configs" / "tiny.json").read_text())
    conf["num_hidden_layers"] = 3
    (bench / "configs" / "tiny-deep.json").write_text(json.dumps(conf))
    mix = json.loads((bench / "traffic" / "tiny-mix.json").read_text())
    mix["rate_per_s"] = 30.0
    (bench / "traffic" / "tiny-fast.json").write_text(json.dumps(mix))
    (bench / "metrics" / "requests_due.py").write_text(NEW_METRIC)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-deep", "source": "x",
                            "file": "bench/configs/tiny-deep.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny-deep-fast", "config":
                              "tiny-deep", "traffic": "tiny-fast",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "requests_due", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "front door", "moves": "ttft_p95_ms",
                              "workloads": ["tiny-deep-fast"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    h = Harness(root)
    cell = h.cell("tiny-deep-fast")
    assert h.config(cell["config"])["num_hidden_layers"] == 3
    assert h.traffic(cell["traffic"])["rate_per_s"] == 30.0
    names = [m["name"] for m in h.metrics("tiny-deep-fast", trace=True)]
    assert "requests_due" in names
    assert "requests_due" not in [
        m["name"] for m in h.metrics("tiny-chat", trace=True)]
    from bench.lib.record import Run
    run = Run(start=0.0, end=1.0, requests=[], steps=[], setup_s=0.0,
              shapes={}, peaks={})
    assert h.reader("requests_due").read(run) == 0.0
    with pytest.raises(KeyError):
        h.cell("no-such-cell")


def test_new_cell_gets_the_existing_metrics_without_an_edit(tmp_path):
    """A cell added as an entry reports every end-to-end metric and every
    per-layer metric that names no cells, as they stand in
    BENCHMARK.json: none of their entries needs its cell added."""
    real = Harness().spec
    root = make_tree(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"], spec["end_to_end"] = real["per_layer"], \
        real["end_to_end"]
    spec["workloads"].append({"name": "tiny-later", "config": "tiny",
                              "traffic": "tiny-mix", "chips": 1,
                              "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    h = Harness(root)
    general = [m["name"] for m in real["per_layer"] if "workloads" not in m]
    assert len(general) >= 5
    got = [m["name"] for m in h.metrics("tiny-later", trace=True)]
    assert got == general
    assert [m["name"] for m in h.metrics("tiny-later", trace=False)] == \
        [m["name"] for m in real["end_to_end"]]


def test_command_refuses_to_measure_without_a_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi6b-chat",
         "--seed", str(2 ** 40 + 1), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr

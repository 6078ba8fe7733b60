"""Each configuration names its model module (bench/models/<model>.py),
the only code that knows its architecture's sizes: a new architecture is
added as files, the draw follows the module's rule, and a configuration
that names no known module is refused."""
import filecmp
import json
import pathlib
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench.lib.harness import Harness  # noqa: E402
from bench.tests.tiny import TINY_CONFIG, make_tree  # noqa: E402

PEAKS = json.loads((REPO / "bench" / "peaks.json").read_text())[
    "TPU v5 lite"]


def run(h, seed):
    from bench.lib.cell import run_cell
    return run_cell(h, "tiny-chat", seed=seed, seconds=2.0, trace=False,
                    peaks=PEAKS, t_start=time.monotonic())


def test_a_model_module_added_as_a_file_runs_correct(tmp_path):
    """The tree's only model module is one the repository does not have;
    the cell runs through it to ``correct``, and ``bench/lib`` is the
    repository's, byte for byte."""
    conf = dict(TINY_CONFIG, model="tree_only")
    root = make_tree(tmp_path, config=conf)
    models = root / "bench" / "models"
    (models / "tree_only.py").write_text(
        (REPO / "bench" / "models" / "llama.py").read_text())
    (models / "llama.py").unlink()
    assert not (REPO / "bench" / "models" / "tree_only.py").exists()

    h = Harness(root)
    r = run(h, 2 ** 40 + 21)
    assert r["correct"], r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    mod = h.model(h.config("tiny"))
    assert pathlib.Path(mod.__file__) == models / "tree_only.py"
    cmp = filecmp.dircmp(REPO / "bench" / "lib", root / "bench" / "lib",
                         ignore=["__pycache__"])
    assert not (cmp.left_only or cmp.right_only or cmp.diff_files)


def test_the_draw_follows_the_model_modules_rule():
    """An MoE configuration's router and qk-norm leaves are drawn once a
    rule names them; the llama rule refuses them by name."""
    import jax
    import numpy as np
    from repro.configs.registry import get_config
    from repro.models import model as M
    from bench.lib.model import draw_params

    llama = Harness().model({"name": "yi-6b", "model": "llama"})
    cfg = get_config("olmoe-1b-7b").smoke()
    with pytest.raises(ValueError, match="no draw rule") as e:
        draw_params(cfg, 5, llama.FAN_IN)
    assert "['moe']['router']" in str(e.value)

    rule = dict(llama.FAN_IN, router=lambda s: s[-2], q_norm=None,
                k_norm=None)
    w = draw_params(cfg, 5, rule)
    specs = M.param_specs(cfg)
    assert jax.tree_util.tree_structure(w) == \
        jax.tree_util.tree_structure(specs)
    for got, want in zip(jax.tree_util.tree_leaves(w),
                         jax.tree_util.tree_leaves(specs)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype)
    router = np.asarray(w["layers"]["moe"]["router"], np.float32)
    d = router.shape[-2]
    assert np.std(router) == pytest.approx(d ** -0.5, rel=0.15)
    assert np.all(np.asarray(w["layers"]["attn"]["q_norm"]) == 1)


@pytest.mark.parametrize("model", [None, "no-such-arch"])
def test_a_config_without_a_known_model_is_refused(tmp_path, model):
    conf = {k: v for k, v in TINY_CONFIG.items() if k != "model"}
    if model is not None:
        conf["model"] = model
    h = Harness(make_tree(tmp_path, config=conf))
    with pytest.raises(KeyError, match=r"known .*\['llama'\]"):
        run(h, 3)

"""Operation and byte counts of the llama model module
(bench/models/llama.py) against hand counts, and the KV pool they size,
for both configurations."""
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench.lib import flops as F  # noqa: E402
from bench.lib.harness import Harness  # noqa: E402
from bench.lib.model import engine_config  # noqa: E402
from bench.lib.record import RequestLog, Run, StepLog  # noqa: E402

# hand counts: per layer wq + wo (d*H*hd each), wk + wv (d*Kv*hd each),
# gate, up and down (d*ff each), two norm scales (d each)
HAND = {
    "yi-6b": dict(
        layer=4096 * 4096 * 2 + 4096 * 512 * 2 + 3 * 4096 * 11008 + 8192,
        params=6_061_035_520, kv_token=32 * 2 * 4 * 128 * 2,
        head=64000 * 4096, L=32, H=32),
    "mistral-nemo-12b": dict(
        layer=5120 * 4096 * 2 + 5120 * 1024 * 2 + 3 * 5120 * 14336 + 10240,
        params=16 * 272_640_000 + 5120 + 2 * 131072 * 5120,
        kv_token=16 * 2 * 8 * 128 * 2, head=131072 * 5120, L=16, H=32),
}


def model_and_config(name):
    h = Harness()
    conf = h.config(name)
    return h.model(conf), conf


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_match_hand_counts(name):
    m, conf = model_and_config(name)
    s = m.shapes(conf)
    hand = HAND[name]
    assert s["layer_params"] == hand["layer"]
    assert m.param_count(s) == hand["params"]
    assert m.kv_bytes_per_token(s) == hand["kv_token"] == 65536
    assert m.attn_flops(s, 1000) == 4 * 1000 * hand["H"] * 128 * hand["L"]
    assert m.token_flops(s, 1000) == \
        2 * (hand["L"] * hand["layer"] + hand["head"]) + \
        m.attn_flops(s, 1000)
    assert m.decode_attn_bytes(s, 1000) == \
        65536 * 1000 + 2 * hand["L"] * hand["H"] * 128 * 2


def test_yi_parameters_match_the_published_count():
    """6.06 B parameters, as the bf16 checkpoint's 12.12 GB says."""
    m, conf = model_and_config("yi-6b")
    assert m.param_count(m.shapes(conf)) * 2 == \
        pytest.approx(12.12e9, rel=1e-3)


@pytest.mark.parametrize("name, pages", [("mistral-nemo-12b", 4196),
                                         ("yi-6b", 3623)])
def test_pool_pages_from_the_model_modules_kv_bytes(name, pages):
    """The pool is the configuration's byte budget over pages of 8
    tokens at 65,536 bytes a token: 1.9 GB (yi) and 2.2 GB (nemo)."""
    m, conf = model_and_config(name)
    ecfg = engine_config(conf, m, 4096, use_kernel=False)
    assert ecfg.cache.page_size == 8
    assert ecfg.cache.num_pages == pages
    assert ecfg.max_lanes == 32


def test_roofline_takes_the_slower_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.least_time_s(1000, 50, peaks) == 10.0
    assert F.least_time_s(100, 50, peaks) == 5.0


def test_step_mfu_counts_prefill_once_and_every_decoded_token():
    m, conf = model_and_config("yi-6b")
    s = m.shapes(conf)
    r = RequestLog(rid=0, due=0.0, prompt_len=600, prefix_len=512,
                   max_new=3, greedy=True, hit_tokens=512)
    steps = [StepLog(1.0, 1.1, ((0, 0, 1),)), StepLog(1.2, 1.3, ((0, 1, 1),)),
             StepLog(1.4, 1.5, ((0, 2, 1),))]
    peaks = {"bf16_flops_per_s": 197e12}
    run = Run(start=0.0, end=2.0, requests=[r], steps=steps, setup_s=0,
              shapes=s, model=m, peaks=peaks)
    prefill = 88 * m.token_flops(s, 0) + \
        m.attn_flops(s, 1) * sum(range(513, 601))
    decode = m.token_flops(s, 601) + m.token_flops(s, 602)
    want = 100 * (prefill + decode) / (0.3 * 197e12)
    assert Harness().reader("step_mfu").read(run) == pytest.approx(want)

"""``paged_roofline`` on a hand-built run: one traced decode program holding
two calls of the paged decode kernel, priced from the step's contexts by
the reader's ``cost``, which is checked against a count by hand; ops of
other names, and kernel events outside any decode program, are not read."""
import json

import pytest

from chipbench.harness import device, paths, program, record
from chipbench.harness import trace as tr
from chipbench.metrics import paged_roofline

CONTEXTS = (100, 1000)


@pytest.fixture(scope="module")
def cfg():
    return json.loads((paths.BENCH / "configs" /
                       "internlm2-20b-1chip.json").read_text())


def test_paged_decode_cost_by_hand(cfg):
    flops, byts = paged_roofline.cost(cfg, CONTEXTS)
    keys = 101 + 1001                      # each row attends over ctx + 1
    assert flops == 4 * 48 * 128 * keys    # QK and PV, 48 heads of 128
    # K and V of every key, 8 KV heads, bf16; q read and o written per row
    assert byts == keys * 8 * 128 * 2 * 2 + 2 * 2 * 48 * 128 * 2


def _run(cfg, ops):
    decode = tr.Event("jit_decode_step", 1.0, 0.02)
    red = tr.Reduced((0.0, 4.0), [decode], ops, 0.02)
    calls = [record.Call(0.999, "decode", contexts=CONTEXTS)]
    return record.Run(cfg=cfg, fam=program.family(cfg),
                      peak=device.peaks("TPU v5 lite"), seconds=4.0,
                      stats={}, engine0={}, engine1={}, calls=[], trace=red,
                      trace_calls=calls)


def test_reads_the_kernel_inside_decode_only(cfg):
    ops = [tr.Event("paged_decode_attention.1 = bf16[32,8,6,128] "
                    "custom-call(...)", 1.001, 20e-6),
           tr.Event("paged_decode_attention.1 = bf16[32,8,6,128] "
                    "custom-call(...)", 1.011, 20e-6),
           # another kernel in the step, and one outside any decode program
           tr.Event("flash_attention.6 = bf16[1,48,512,128] custom-call("
                    "paged_decode_attention.1)", 1.012, 1.0),
           tr.Event("paged_decode_attention.1 = bf16[32,8,6,128] "
                    "custom-call(...)", 3.0, 1.0)]
    keys = 101 + 1001
    byts = keys * 8 * 128 * 2 * 2 + 2 * 2 * 48 * 128 * 2
    least = byts / 819e9                   # bytes bound it at these sizes
    assert 4 * 48 * 128 * keys / 197e12 < least
    got = paged_roofline.read(_run(cfg, ops))
    assert got == pytest.approx(100.0 * 2 * least / 40e-6)
    assert 0 < got <= 100


def test_silent_without_the_kernel(cfg):
    ops = [tr.Event("fusion.170 = bf16[4096,16,8,128] fusion(...)", 1.001,
                    3e-3),
           tr.Event("flash_attention.6 = bf16[1,48,512,128] custom-call()",
                    1.005, 1e-3)]
    assert paged_roofline.read(_run(cfg, ops)) is None

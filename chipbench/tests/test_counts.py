"""The benchmark's shape-based counts against the program's own shapes and
against the sizes reckoned for the configuration by hand: 4.258 B
parameters, a 2.15 GB KV pool of 32 rows x 2048 tokens; and its peak
table."""
import json

import jax
import numpy as np
import pytest

from chipbench.harness import device, paths, program
from repro.models import decode_cache_paged, model_specs
from repro.models.common import param_count


def _cfg(name):
    return json.loads((paths.BENCH / "configs" / f"{name}.json").read_text())


def _bytes(tree):
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("name,params", [("internlm2-20b-1chip", 4_257_847_296)])
def test_param_count(name, params):
    cfg = _cfg(name)
    fam = program.family(cfg)
    assert fam.param_count(cfg) == params
    assert param_count(model_specs(program.arch_config(cfg))) == params


def test_internlm2_kv_pool():
    cfg = _cfg("internlm2-20b-1chip")
    fam, arch, e = program.family(cfg), program.arch_config(cfg), cfg["engine"]
    rows, seq, page = e["batch_size"], e["max_seq"], e["page_size"]
    pool = rows * fam.state_bytes_per_row(cfg, seq)
    assert pool == 2 ** 31                       # 2.15 GB
    pages = rows * seq // page
    cache = decode_cache_paged(arch, rows, seq, pages, page, abstract=True)
    # the program's pool holds one page more, its null page
    assert _bytes(cache) == pool * (pages + 1) // pages


@pytest.mark.parametrize("name", ["internlm2-20b-1chip"])
def test_step_costs(name):
    """Prefill and decode operations are two per weight per token plus the
    sequence mixing; decode reads every weight once."""
    cfg = _cfg(name)
    fam = program.family(cfg)
    a = cfg["arch"]
    head = a["d_model"] * a["vocab_size"]
    body = fam.param_count(cfg) - 2 * head       # embedding and head out
    f1, b1 = fam.prefill_cost(cfg, 1024)
    assert 2 * 1024 * body < f1 < 2 * 1024 * body * 1.15
    f2, b2 = fam.decode_cost(cfg, [100, 1000])
    assert 2 * 2 * (body + head) < f2 < 2 * 2 * (body + head) * 1.05
    assert b2 > 2 * (body + head)
    assert fam.decode_cost(cfg, [100])[1] < b2


def test_flash_cost():
    cfg = _cfg("internlm2-20b-1chip")
    fam = program.family(cfg)
    flops, byts = fam.flash_cost(cfg, 1024)
    assert flops == 2 * 48 * 128 * 1024 * 1025   # QK and PV, causal half
    assert byts == 2 * 1024 * 128 * (2 * 48 + 2 * 8)
    assert fam.flash_calls_per_prefill(cfg) == 8


def test_peaks():
    assert device.peaks("TPU v5 lite") == {"flops": 197e12,
                                           "hbm_bytes_s": 819e9}
    with pytest.raises(KeyError):
        device.peaks("cpu")

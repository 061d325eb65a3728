"""Tiny stand-ins for the cells' files, for the harness's tests on the CPU:
the same families and engine flags at toy widths."""
import copy
import json

from chipbench.harness import paths

#: the CPU has no peak table entry; any positive numbers do for the tests
PEAK = {"flops": 1e12, "hbm_bytes_s": 1e11}

_SMALL = {
    "internlm2-20b-1chip": dict(num_layers=2, d_model=64, num_heads=4,
                                num_kv_heads=2, d_ff=128, vocab_size=256),
}


def bench():
    return json.loads((paths.ROOT / "BENCHMARK.json").read_text())


def config(name, limit=None):
    cfg = json.loads((paths.BENCH / "configs" / f"{name}.json").read_text())
    cfg = copy.deepcopy(cfg)
    cfg["arch"].update(_SMALL[name])
    cfg["engine"].update(batch_size=4, max_seq=128)
    if limit is not None:
        cfg["check"]["max_logit_gap"] = limit
    cfg["check"].update(sample_requests=64)
    return cfg


def traffic(name):
    spec = json.loads((paths.BENCH / "traffic" / "chat.json").read_text())
    spec["prompt"] = {"median": 40, "sigma": 0.5, "min": 1, "max": 64,
                      "buckets": [32, 64]}
    spec["output"] = {"median": 6, "sigma": 0.5, "min": 3, "max": 10}
    spec["rate_rps"] = 4.0
    spec["lead_in_s"] = 0.5
    return spec

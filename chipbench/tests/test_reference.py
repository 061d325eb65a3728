"""The plain float32 references against the program's own jnp path, at toy
widths on the CPU, in float32: the program's prefill logits of each prefix
of a sequence equal the reference's logits at that position."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import program
from repro.models import build_prefill_step

import tiny

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(eq, a, b):
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def _reference_logits(fam, cfg, w, tokens):
    x = fam.embed(w, jnp.asarray(tokens))
    blk = fam.blocks(w)
    for li in range(cfg["arch"]["num_layers"]):
        p = jax.tree.map(lambda a: a[li].astype(jnp.float32), blk)
        x = fam.layer(p, x, cfg, _mm)
    return _mm("sd,dv->sv", fam.final(w, x, cfg), fam.unembed(w))


@pytest.mark.parametrize("name", ["internlm2-20b-1chip"])
def test_reference_matches_program_f32(name):
    cfg = tiny.config(name)
    cfg["arch"].update(param_dtype="float32", compute_dtype="float32",
                       use_pallas=False)
    arch = program.arch_config(cfg)
    fam = program.family(cfg)
    w = program.draw_weights(cfg, arch, seed=7)
    tokens = np.random.default_rng(0).integers(1, arch.vocab_size, 40)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(_reference_logits(fam, cfg, w, tokens))
        prefill = jax.jit(build_prefill_step(arch))
        for n in (1, 7, 32, 40):
            _, got = prefill(w, {"tokens": jnp.asarray(tokens[None, :n])})
            want = ref[n - 1]
            err = np.max(np.abs(np.asarray(got[0]) - want))
            assert err < 1e-4 * max(1.0, np.max(np.abs(want))), (n, err)

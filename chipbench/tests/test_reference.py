"""The plain references at small size, on the CPU: the StableLM forward
against the program's model in float32, the blocked stencil oracle
against the program's oracle on the whole grid, and the weights drawn
layer by layer against the stacked draw."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import fields, weights
from chipbench.reference import stencil
from chipbench.reference.stablelm import Reference, served_batch, widest_gap

TINY = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "vocab_size": 128, "rope_pct": 0.25, "rope_theta": 10000,
        "norm_eps": 1e-5, "use_qkv_bias": False, "torch_dtype": "float32",
        "model_type": "tiny"}


def test_layer_draws_equal_the_stacked_draw():
    p = weights.make_params(TINY, 12345)
    for l in range(TINY["num_hidden_layers"]):
        one = weights.layer_weights(TINY, jnp.uint32(12345), l)
        for k, v in one.items():
            assert (np.asarray(v) == np.asarray(p[f"layers/{k}"][l])).all(), k
    g = weights.global_weights(TINY, jnp.uint32(12345))
    assert (np.asarray(g["lm_head"]) == np.asarray(p["lm_head"])).all()


def test_reference_matches_the_program_forward_in_float32():
    from jax.sharding import PartitionSpec as P
    from repro.core.compat import make_mesh, shard_map
    from repro.distributed.sharding import rules_for_ctx
    from repro.models import schema as sch
    from repro.models.config import ParallelCtx
    from repro.models.transformer import transformer_forward
    from chipbench.serving import model_config

    mcfg = model_config(TINY)
    mesh = make_mesh((1, 1), ("data", "model"), axis_types="auto",
                     devices=jax.devices()[:1])
    ctx = ParallelCtx.from_mesh(mesh, remat=False, inference=True)
    params = weights.make_params(TINY, 7, dtype=jnp.float32)
    pspecs = sch.partition_specs(mcfg, mesh, rules_for_ctx(ctx))

    def fwd(p, t):
        h, _ = transformer_forward(p, t, mcfg, ctx)
        return jnp.einsum("btd,dv->btv", h, p["lm_head"],
                          precision=jax.lax.Precision.HIGHEST)

    prog = jax.jit(shard_map(fwd, mesh=mesh, in_specs=(pspecs, P()),
                             out_specs=P()))
    tokens = np.random.default_rng(0).integers(0, 128, (2, 24)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(prog(params, jnp.asarray(tokens)))
    # the reference draws its weights in bf16 (as served); compare with the
    # program run on those same bf16-rounded values
    ref = Reference(TINY, "highest")
    where = [(b, t) for b in range(2) for t in range(24)]
    params16 = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
                for k, v in weights.make_params(TINY, 7).items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(prog(params16, jnp.asarray(tokens)))
    got = np.asarray(ref.logits(7, tokens, where)).reshape(2, 24, -1)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_widest_gap_and_batch_packing():
    tokens, where, chosen = served_batch([np.array([5, 6, 7])], [[1, 2, 3]],
                                         8)
    assert tokens[0, :5].tolist() == [5, 6, 7, 1, 2]
    assert where == [(0, 2), (0, 3), (0, 4)] and chosen.tolist() == [1, 2, 3]
    logits = np.zeros((3, 4))
    logits[:, 0] = 1.0
    logits[1, 2] = 0.25
    assert widest_gap(logits, np.array([0, 2, 0])) == 0.75


def test_blocked_oracle_equals_the_whole_grid_oracle():
    from repro.kernels.stencil.ref import wave_step_ref

    cfg = {"nz": 48, "ny": 16, "nx": 24, "dx": 1.0, "velocity_layers": 3,
           "c2dt2_min": 0.05, "c2dt2_max": 0.12}
    K = 3
    prof = fields.velocity_profile(cfg, 99)
    u, up, c2 = fields.planes(jax.random.PRNGKey(5), jnp.arange(48),
                              jnp.asarray(prof), 16, 24)
    assert 0.05 <= float(c2.min()) and float(c2.max()) <= 0.12
    for _ in range(K):
        u, up = wave_step_ref(u, up, c2), u
    blocked = stencil.BlockedOracle(cfg, K, 16)
    got = np.concatenate([np.asarray(blocked.block_field(5, prof, a, b))
                          for a, b in blocked.blocks()])
    assert np.abs(got - np.asarray(u)).max() <= 1e-6 * np.abs(u).max()
    low = stencil.BlockedOracle(cfg, K, 16, dtype=jnp.bfloat16)
    lo = np.concatenate([np.asarray(low.block_field(5, prof, a, b))
                         for a, b in low.blocks()])
    assert np.abs(lo - np.asarray(u)).max() > 1e-3 * np.abs(u).max()

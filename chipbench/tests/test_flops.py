"""FLOP and byte functions against the program's own parameter count and
the grid's shapes."""

from chipbench import flops, spec as S


def _cfg():
    return S.load_json(S.ROOT / "chipbench" / "configs" / "stablelm-3b.json")


def test_matmul_params_match_the_program_schema():
    from chipbench.serving import model_config

    cfg = _cfg()
    mcfg = model_config(cfg)
    d, V, L = cfg["hidden_size"], cfg["vocab_size"], cfg["num_hidden_layers"]
    norms = (2 * L + 1) * d
    total = mcfg.param_count()
    assert total == flops.matmul_params(cfg) + 2 * d * V + norms
    assert abs(total - 2.795e9) < 0.001e9       # published 2.8B


def test_token_flops_count_weights_attention_and_head():
    cfg = _cfg()
    base = 2.0 * flops.matmul_params(cfg)
    hd = flops.head_dim(cfg)
    assert hd == 80
    one = flops.token_flops(cfg, 1, logits=False)
    assert one == base + 4 * 32 * 32 * 80
    assert flops.token_flops(cfg, 100, logits=True) - flops.token_flops(
        cfg, 100, logits=False) == 2 * 2560 * 50304


def test_prefill_flops_is_the_sum_over_positions():
    cfg = _cfg()
    want = sum(flops.token_flops(cfg, p + 1, logits=False)
               for p in range(37, 165))
    assert abs(flops.prefill_flops(cfg, 37, 165) - want) < 1e-6 * want
    assert flops.prefill_flops(cfg, 5, 5) == 0.0


def test_stencil_bytes_per_cell():
    cfg = S.load_json(S.ROOT / "chipbench" / "configs" / "minimod-1024-z256.json")
    assert flops.stencil_bytes_per_cell() == 16
    per_chip = cfg["nz"] // cfg["z_split"] * cfg["ny"] * cfg["nx"]
    assert per_chip * 4 == 2**30                # 1 GiB per field per chip

"""bench/work counts against the GEMMs a smoke configuration of each
family runs (the program's own shape table serves as the witness here;
the benchmark never reads it)."""

import dataclasses

import pytest

from repro.configs import get_config
from repro.models.config import gemm_shape_counts

import smoke
from work import common as wc
from work import dense, moe

FAMILIES = {"dense": dense, "moe": moe}


def program_config(family):
    registry, model = smoke.SMOKE_MODELS[family]
    over = {k: v for k, v in model.items() if k != "n_kv_heads"}
    over["n_kv_heads"] = model["n_kv_heads"]
    return dataclasses.replace(get_config(registry), **over), model


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("rows", [1, 8, 96])
def test_row_flops_match_program_gemms(family, rows):
    cfg, m = program_config(family)
    work = FAMILIES[family]
    prog = sum(wc.gemm_flops(M, N, K) * c for (M, N, K), c in
               gemm_shape_counts(cfg, rows, head_tokens=0).items())
    router = (rows * m["n_layers"] * wc.gemm_flops(1, m["n_experts"],
                                                   m["d_model"])
              if family == "moe" else 0.0)
    assert rows * work.row_flops(m) == pytest.approx(prog + router)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_weight_bytes_cover_every_matrix(family):
    cfg, m = program_config(family)
    work = FAMILIES[family]
    n = cfg.n_params()
    embed = m["vocab"] * m["d_model"]
    # with every expert reached, the weights read are every matrix but the
    # embedding table, in bf16 (n_params counts the final norm scale and
    # no other norm)
    many = 10**6
    got = work.weight_bytes(m, many) - 2 * many * m["d_model"]
    assert got == pytest.approx(2 * (n - embed - m["d_model"]), rel=1e-6)


def test_prompt_attention_is_causal():
    m = smoke.SMOKE_MODELS["dense"][1]
    one = wc.attention_flops(m, 1) * m["n_layers"]
    assert wc.prompt_attention_flops(m, 4) == pytest.approx(one * 10)

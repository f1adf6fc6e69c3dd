"""Serving-engine tests: termination bugfixes (EOS-as-first-token, budget
of one), padding parity, continuous-batching slot refill, wave-vs-continuous
token-stream equality, telemetry and per-request energy accounting."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.models.config import ModelConfig, gemm_shape_counts, gemm_shapes
from repro.models.registry import get_model
from repro.serving.engine import Request, ServingEngine


def tiny_cfg(**kw) -> ModelConfig:
    base = dict(
        name="serve-test", kind="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=256, param_dtype="float32",
        activation_dtype="float32", remat=False,
    )
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def served():
    cfg = tiny_cfg()
    model = get_model(cfg)
    params = model.init(jax.random.key(0), cfg)
    return cfg, model, params


def make_engine(served, **kw):
    cfg, model, params = served
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_len", 64)
    return ServingEngine(model, params, cfg, **kw)


def prompt(seed: int, n: int, vocab: int = 256) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, vocab, n).astype(np.int32)


def greedy_tokens(served, p: np.ndarray, mode: str = "continuous",
                  **req_kw) -> np.ndarray:
    eng = make_engine(served, mode=mode)
    eng.submit(Request(uid=0, prompt=p.copy(), **req_kw))
    (res,) = eng.run_until_empty()
    return res.tokens


# ---------------------------------------------------------------------------
# termination bugfixes
# ---------------------------------------------------------------------------


class TestTermination:
    @pytest.mark.parametrize("mode", ["wave", "continuous"])
    def test_eos_as_first_token_stops_immediately(self, served, mode):
        """Regression for the wave loop appending the first sampled token
        with no done-check: an EOS emitted as the *first* token must end
        the request at one token, not run to full budget."""
        p = prompt(0, 8)
        first = int(greedy_tokens(served, p, max_new_tokens=8)[0])
        eng = make_engine(served, mode=mode)
        eng.submit(Request(uid=0, prompt=p.copy(), max_new_tokens=8,
                           eos_id=first))
        (res,) = eng.run_until_empty()
        assert res.n_tokens == 1
        assert res.tokens.tolist() == [first]
        assert res.steps == 0          # never occupied a decode step

    @pytest.mark.parametrize("mode", ["wave", "continuous"])
    def test_max_new_tokens_one(self, served, mode):
        eng = make_engine(served, mode=mode)
        eng.submit(Request(uid=0, prompt=prompt(1, 6), max_new_tokens=1))
        (res,) = eng.run_until_empty()
        assert res.n_tokens == 1 and len(res.tokens) == 1

    def test_mixed_budgets_in_one_batch(self, served):
        """Short-budget requests must stop at their own budget even when
        batched with longer ones — in both modes, with equal streams."""
        budgets = [2, 7, 3, 5]
        per_mode = {}
        for mode in ("wave", "continuous"):
            eng = make_engine(served, mode=mode)
            for uid, b in enumerate(budgets):
                eng.submit(Request(uid=uid, prompt=prompt(10 + uid, 5),
                                   max_new_tokens=b))
            per_mode[mode] = {r.uid: r for r in eng.run_until_empty()}
        for uid, b in enumerate(budgets):
            for mode in per_mode:
                assert per_mode[mode][uid].n_tokens == b
            np.testing.assert_array_equal(
                per_mode["wave"][uid].tokens,
                per_mode["continuous"][uid].tokens)

    def test_prompt_must_fit_max_len(self, served):
        eng = make_engine(served, max_len=16)
        with pytest.raises(ValueError):
            eng.submit(Request(uid=0, prompt=prompt(2, 16)))

    def test_budget_clamped_to_kv_room(self, served):
        """A budget larger than the remaining KV room is clamped, not
        allowed to scribble past max_len."""
        eng = make_engine(served, max_len=16, mode="continuous")
        eng.submit(Request(uid=0, prompt=prompt(3, 12), max_new_tokens=64))
        (res,) = eng.run_until_empty()
        assert res.n_tokens == 16 - 12


# ---------------------------------------------------------------------------
# steps vs n_tokens (energy denominator)
# ---------------------------------------------------------------------------


class TestStepsAccounting:
    def test_wave_steps_count_residency_not_tokens(self, served):
        """Old Result.steps reported len(tokens). A 2-token request riding
        a wave with an 8-token request stays resident for the whole wave:
        steps must reflect the executed decode iterations, n_tokens the
        generated count."""
        eng = make_engine(served, mode="wave")
        eng.submit(Request(uid=0, prompt=prompt(20, 4), max_new_tokens=2))
        eng.submit(Request(uid=1, prompt=prompt(21, 4), max_new_tokens=8))
        res = {r.uid: r for r in eng.run_until_empty()}
        assert res[0].n_tokens == 2 and res[1].n_tokens == 8
        # wave runs 7 decode iterations (first token comes from prefill)
        assert res[0].steps == res[1].steps == 7

    def test_continuous_steps_stop_at_retirement(self, served):
        eng = make_engine(served, mode="continuous")
        eng.submit(Request(uid=0, prompt=prompt(20, 4), max_new_tokens=2))
        eng.submit(Request(uid=1, prompt=prompt(21, 4), max_new_tokens=8))
        res = {r.uid: r for r in eng.run_until_empty()}
        assert res[0].n_tokens == 2 and res[0].steps == 1
        assert res[1].n_tokens == 8 and res[1].steps == 7


# ---------------------------------------------------------------------------
# padding parity
# ---------------------------------------------------------------------------


class TestPaddingParity:
    def test_short_prompt_alone_vs_padded_in_batch(self, served):
        """A short prompt served alone must produce the same greedy tokens
        as the same prompt padded into a batch with a much longer one —
        the prefill mask/length threading contract."""
        short, long_ = prompt(30, 5), prompt(31, 21)
        alone = greedy_tokens(served, short, max_new_tokens=8)
        for mode in ("wave", "continuous"):
            eng = make_engine(served, mode=mode)
            eng.submit(Request(uid=0, prompt=short.copy(),
                               max_new_tokens=8))
            eng.submit(Request(uid=1, prompt=long_.copy(),
                               max_new_tokens=8))
            res = {r.uid: r for r in eng.run_until_empty()}
            np.testing.assert_array_equal(res[0].tokens, alone, err_msg=mode)

    def test_slot_prefill_bucket_padding_is_invisible(self, served):
        """Bucketed right-padding (pow2 slot prefill) must not change
        generations: lengths just under and just over a bucket edge."""
        for n in (7, 8, 9):
            p = prompt(40 + n, n)
            a = greedy_tokens(served, p, mode="continuous",
                              max_new_tokens=6)
            b = greedy_tokens(served, p, mode="wave", max_new_tokens=6)
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------


def mixed_workload(n=9, seed=0, vocab=256):
    rng = np.random.default_rng(seed)
    return [
        (uid, rng.integers(0, vocab, rng.integers(4, 12)).astype(np.int32),
         int(rng.choice([4, 8, 16])))
        for uid in range(n)
    ]


class TestContinuousBatching:
    def _serve(self, served, mode, reqs, max_batch=3):
        eng = make_engine(served, mode=mode, max_batch=max_batch)
        for uid, p, mnt in reqs:
            eng.submit(Request(uid=uid, prompt=p.copy(),
                               max_new_tokens=mnt))
        return eng, {r.uid: r for r in eng.run_until_empty()}

    def test_slot_refill_mid_decode(self, served):
        """More requests than slots with mixed budgets: a request admitted
        mid-decode (after a short one retires) completes correctly."""
        reqs = mixed_workload()
        eng, res = self._serve(served, "continuous", reqs)
        assert set(res) == {uid for uid, _, _ in reqs}
        for uid, p, mnt in reqs:
            assert res[uid].n_tokens == mnt
            np.testing.assert_array_equal(
                res[uid].tokens,
                greedy_tokens(served, p, max_new_tokens=mnt))

    def test_streams_bit_identical_and_fewer_slot_steps(self, served):
        """Acceptance: identical greedy streams between modes, with
        measurably fewer executed decode-step*slots in continuous mode."""
        reqs = mixed_workload()
        ec, rc = self._serve(served, "continuous", reqs)
        ew, rw = self._serve(served, "wave", reqs)
        for uid in rw:
            np.testing.assert_array_equal(rc[uid].tokens, rw[uid].tokens)
        assert ec.report()["decode_steps"] < ew.report()["decode_steps"]
        assert ec.report()["slot_steps"] < ew.report()["slot_steps"]

    def test_single_slot_engine(self, served):
        reqs = mixed_workload(n=3, seed=5)
        _, res = self._serve(served, "continuous", reqs, max_batch=1)
        for uid, p, mnt in reqs:
            np.testing.assert_array_equal(
                res[uid].tokens,
                greedy_tokens(served, p, max_new_tokens=mnt))

    def test_small_max_len_engine(self, served):
        """max_len below the smallest pow2 bucket: the batch-axis probe
        and bucketing must use real (max_len-clamped) shapes."""
        eng = make_engine(served, max_len=6, mode="continuous")
        eng.submit(Request(uid=0, prompt=prompt(55, 3), max_new_tokens=3))
        eng.submit(Request(uid=1, prompt=prompt(56, 4), max_new_tokens=2))
        res = {r.uid: r for r in eng.run_until_empty()}
        assert res[0].n_tokens == 3 and res[1].n_tokens == 2
        np.testing.assert_array_equal(
            res[0].tokens,
            greedy_tokens(served, prompt(55, 3), max_new_tokens=3))

    def test_first_token_finisher_frees_slot_same_pass(self, served):
        """An admission that finishes on its first sampled token must not
        leave its slot dead for the next decode step when the queue still
        has work: the refill loop keeps admitting into the freed slot."""
        p_eos = prompt(57, 5)
        eos = int(greedy_tokens(served, p_eos, max_new_tokens=4)[0])
        eng = make_engine(served, mode="continuous", max_batch=2)
        eng.submit(Request(uid=0, prompt=prompt(58, 5), max_new_tokens=4))
        eng.submit(Request(uid=1, prompt=p_eos.copy(), max_new_tokens=4,
                           eos_id=eos))
        eng.submit(Request(uid=2, prompt=prompt(59, 5), max_new_tokens=4))
        res = {r.uid: r for r in eng.run_until_empty()}
        assert res[1].n_tokens == 1
        # uid2 takes uid1's slot in the same refill pass, so every decode
        # step runs with both slots live
        assert eng.report()["slot_occupancy"] == 1.0

    def test_auto_mode_picks_continuous_for_dense(self, served):
        eng = make_engine(served)
        assert eng._continuous_supported()
        eng.submit(Request(uid=0, prompt=prompt(50, 4), max_new_tokens=3))
        assert len(eng.run_until_empty()) == 1
        assert eng.report()["slot_occupancy"] > 0

    def test_wave_api_still_packs_max_batch(self, served):
        eng = make_engine(served)
        for uid in range(5):
            eng.submit(Request(uid=uid, prompt=np.arange(4, dtype=np.int32),
                               max_new_tokens=2))
        first_wave = eng.run_wave()
        assert len(first_wave) == 2
        assert len(eng.queue) == 3

    @pytest.mark.parametrize("mode", ["wave", "continuous"])
    def test_nongreedy_ignores_dead_slots(self, served, mode):
        """A request's sampled stream must not depend on its neighbors:
        per-request RNG streams mean retiring a companion earlier (or
        serving alone) cannot shift the survivor's draws."""

        def sampled(companion_budget):
            eng = make_engine(served, greedy=False, seed=7, mode=mode)
            eng.submit(Request(uid=0, prompt=prompt(60, 6),
                               max_new_tokens=6))
            if companion_budget:
                eng.submit(Request(uid=1, prompt=prompt(61, 6),
                                   max_new_tokens=companion_budget))
            return {r.uid: r.tokens for r in eng.run_until_empty()}

        base = sampled(6)
        np.testing.assert_array_equal(base[0], sampled(6)[0])  # determinism
        # shorter-lived companion -> dead slot mid-serve; stream unchanged
        np.testing.assert_array_equal(base[0], sampled(2)[0])
        # no companion at all
        np.testing.assert_array_equal(base[0], sampled(0)[0])


class TestMoEFamilies:
    """The other CONTINUOUS_KINDS: continuous/wave bit-parity for MoE and
    MLA-MoE (capacity sized not to bind — the documented condition)."""

    def _cfg(self, kind):
        base = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                    vocab=128, param_dtype="float32",
                    activation_dtype="float32", remat=False,
                    capacity_factor=16.0, n_experts=4, top_k=2,
                    d_ff_expert=64)
        if kind == "moe":
            return tiny_cfg(kind="moe", d_ff=0, **base)
        return tiny_cfg(kind="mla_moe", d_ff=128, n_shared_experts=1,
                        kv_lora_rank=16, rope_head_dim=8, **base)

    @pytest.mark.parametrize("kind", ["moe", "mla_moe"])
    def test_continuous_matches_wave_with_slot_refill(self, kind):
        cfg = self._cfg(kind)
        model = get_model(cfg)
        params = model.init(jax.random.key(0), cfg)
        rng = np.random.default_rng(1)
        reqs = [(uid, rng.integers(0, cfg.vocab,
                                   rng.integers(4, 10)).astype(np.int32),
                 int(rng.choice([3, 6]))) for uid in range(4)]
        outs = {}
        for mode in ("continuous", "wave"):
            eng = ServingEngine(model, params, cfg, max_batch=2,
                                max_len=32, mode=mode)
            assert eng._continuous_supported()
            for uid, p, mnt in reqs:
                eng.submit(Request(uid=uid, prompt=p.copy(),
                                   max_new_tokens=mnt))
            outs[mode] = {r.uid: r for r in eng.run_until_empty()}
        for uid, _, mnt in reqs:
            assert outs["continuous"][uid].n_tokens == mnt
            np.testing.assert_array_equal(
                outs["continuous"][uid].tokens, outs["wave"][uid].tokens)

    def test_mla_counts_match_traced_projections(self):
        """MLA serves via its latent fleet (w_uq/w_dkv/w_kpe + cache-wide
        w_uk/w_uv), never the generic Q/K/V skeleton."""
        cfg = self._cfg("mla_moe")
        counts = gemm_shape_counts(cfg, 4, kv_rows=64)
        d, hd, pe = cfg.d_model, cfg.hd, cfg.rope_head_dim
        r = cfg.kv_lora_rank
        assert (4, cfg.n_heads * (hd + pe), d) in counts      # w_uq
        assert (4, r, d) in counts                            # w_dkv
        assert (4, pe, d) in counts                           # w_kpe
        assert counts[(64, cfg.n_heads * hd, r)] == \
            2 * cfg.n_layers                                  # w_uk/w_uv
        assert (4, cfg.kv_heads * hd, d) not in counts        # no K/V proj


class TestSsmFamilies:
    """mamba1/mamba2/hybrid are promoted out of the wave-mode fallback:
    chunked admission carries conv/scan state across chunk boundaries
    bit-exactly, so they serve continuously with wave-parity streams."""

    def _mamba(self):
        cfg = tiny_cfg(kind="mamba1", n_layers=2, d_ff=0, ssm_state=8,
                       expand=2, d_conv=4)
        model = get_model(cfg)
        return cfg, model, model.init(jax.random.key(0), cfg)

    def test_mamba_serves_continuously_with_wave_parity(self):
        cfg, model, params = self._mamba()
        reqs = [(uid, prompt(uid, 6, cfg.vocab), 4) for uid in range(3)]
        outs = {}
        for mode in ("continuous", "wave"):
            eng = ServingEngine(model, params, cfg, max_batch=2,
                                max_len=32, mode=mode)
            assert eng._continuous_supported()
            for uid, p, mnt in reqs:
                eng.submit(Request(uid=uid, prompt=p.copy(),
                                   max_new_tokens=mnt))
            outs[mode] = {r.uid: r for r in eng.run_until_empty()}
        for uid, _, mnt in reqs:
            assert outs["continuous"][uid].n_tokens == mnt
            np.testing.assert_array_equal(
                outs["continuous"][uid].tokens, outs["wave"][uid].tokens)

    @pytest.mark.parametrize("mode", ["wave", "continuous"])
    def test_attention_free_budget_not_clamped_by_max_len(self, mode):
        """SSM decode state is O(1) per token — no KV cache to run out
        of — so neither the prompt-length check nor the KV-room budget
        clamp applies to mamba1, even in a padded batch."""
        cfg, model, params = self._mamba()
        eng = ServingEngine(model, params, cfg, max_batch=2, max_len=32,
                            mode=mode)
        eng.submit(Request(uid=0, prompt=prompt(0, 28, cfg.vocab),
                           max_new_tokens=20))
        eng.submit(Request(uid=1, prompt=prompt(1, 6, cfg.vocab),
                           max_new_tokens=20))
        res = {r.uid: r for r in eng.run_until_empty()}
        assert res[0].n_tokens == 20
        assert res[1].n_tokens == 20

    def test_hybrid_budgets_clamp_per_row(self):
        """Hybrid now follows the right-padded `lengths` contract in both
        modes: each row's KV room is max_len - its *own* prompt length
        (the legacy left-pad shared-index clamp no longer applies)."""
        cfg = tiny_cfg(kind="hybrid", n_layers=2, d_ff=128, ssm_state=8,
                       expand=2, ssm_headdim=16, ssm_ngroups=1,
                       attn_every=2)
        model = get_model(cfg)
        params = model.init(jax.random.key(0), cfg)
        for mode in ("wave", "continuous"):
            eng = ServingEngine(model, params, cfg, max_batch=2,
                                max_len=32, mode=mode)
            assert eng._continuous_supported()
            eng.submit(Request(uid=0, prompt=prompt(0, 28, cfg.vocab),
                               max_new_tokens=20))
            eng.submit(Request(uid=1, prompt=prompt(1, 6, cfg.vocab),
                               max_new_tokens=20))
            res = {r.uid: r for r in eng.run_until_empty()}
            assert res[0].n_tokens == 32 - 28, mode
            assert res[1].n_tokens == 20, mode


# ---------------------------------------------------------------------------
# telemetry + energy accounting
# ---------------------------------------------------------------------------


class TestTelemetryAndEnergy:
    def test_result_telemetry_fields(self, served):
        eng = make_engine(served, mode="continuous")
        eng.submit(Request(uid=0, prompt=prompt(70, 6), max_new_tokens=5))
        (res,) = eng.run_until_empty()
        assert res.n_tokens == 5
        assert res.ttft_s >= res.queue_s >= 0
        assert res.tokens_per_s > 0
        assert res.energy_j > 0
        assert res.energy_per_token_j == pytest.approx(
            res.energy_j / res.n_tokens)

    def test_engine_report_fields(self, served):
        reqs = mixed_workload(n=6, seed=3)
        eng = make_engine(served, mode="continuous", max_batch=3)
        for uid, p, mnt in reqs:
            eng.submit(Request(uid=uid, prompt=p.copy(),
                               max_new_tokens=mnt))
        results = eng.run_until_empty()
        rep = eng.report()
        assert rep["requests"] == 6
        assert rep["generated_tokens"] == sum(r.n_tokens for r in results)
        assert 0 < rep["slot_occupancy"] <= 1
        assert rep["tokens_per_s"] > 0
        assert rep["j_per_token"] > 0
        # requests carry their attributed share; dead-slot decode spend is
        # charged to the engine so totals stay comparable with wave mode
        assert rep["attributed_energy_j"] == pytest.approx(
            sum(r.energy_j for r in results))
        assert rep["energy_j"] == pytest.approx(
            rep["attributed_energy_j"] + rep["idle_energy_j"])
        assert rep["idle_energy_j"] >= 0

    def test_weight_reads_in_place_under_pallas(self, served, monkeypatch):
        """Every Pallas GEMM of the serving programs reads its weight
        where it lives: each layer's by index into the stacked blocks,
        the head as it is. None reads a copy made before the call."""
        from repro.kernels import ops
        from repro.kernels.tiled_matmul import BlockConfig

        kernel, by_index = ops.tiled_matmul, []

        def counted(*a, **kw):
            by_index.append(kw.get("layer") is not None)
            return kernel(*a, **kw)

        monkeypatch.setattr(ops, "tiled_matmul", counted)
        monkeypatch.setattr(ops, "_tuned_config",
                            lambda *_: BlockConfig(16, 128, 128))
        ops.force_mode("pallas_interpret")
        try:
            eng = make_engine(served)
            eng.submit(Request(uid=0, prompt=prompt(0, 11),
                               max_new_tokens=3))
            eng.run_until_empty()
        finally:
            ops.force_mode("auto")
        assert eng.report()["weight_reads"] == {"in_place": len(by_index),
                                                "staged": 0}
        assert any(by_index) and not all(by_index)

    def test_continuous_beats_wave_on_j_per_token(self, served):
        """The Racing-to-Idle claim: on a mixed-budget workload the wave
        engine attributes strictly more energy per generated token."""
        reqs = mixed_workload()
        rep = {}
        for mode in ("continuous", "wave"):
            eng = make_engine(served, mode=mode, max_batch=3)
            for uid, p, mnt in reqs:
                eng.submit(Request(uid=uid, prompt=p.copy(),
                                   max_new_tokens=mnt))
            eng.run_until_empty()
            rep[mode] = eng.report()
        assert rep["continuous"]["j_per_token"] < rep["wave"]["j_per_token"]

    def test_chip_typo_raises_at_construction(self, served):
        """An unknown chip must fail loudly up front, not silently zero
        every energy estimate."""
        cfg, model, params = served
        with pytest.raises(ValueError):
            ServingEngine(model, params, cfg, chip="tpuv5e")

    def test_step_energy_estimates_scale_with_rows(self, served):
        from repro.core.energy import gemm_fleet_energy

        cfg, _, _ = served
        small = gemm_fleet_energy(gemm_shape_counts(cfg, 8),
                                  chip="tpu_v5e", dtype="bfloat16")
        big = gemm_fleet_energy(gemm_shape_counts(cfg, 4096),
                                chip="tpu_v5e", dtype="bfloat16")
        assert big.energy_j > small.energy_j > 0
        assert big.step_s > small.step_s > 0
        assert small.power_w <= big.power_w or small.power_w > 0

    def test_hybrid_counts_match_traced_in_proj(self):
        """The hybrid (mamba2/SSD) in_proj GEMM carries B/C state
        projections and the dt channel — the fleet must contain the shape
        the model actually traces, not mamba1's 2*d_inner."""
        # vocab != 2*d_inner, else the LM-head shape collides with the
        # mamba1-style in_proj this test asserts is absent
        cfg = tiny_cfg(kind="hybrid", n_layers=4, d_ff=128, ssm_state=8,
                       expand=2, ssm_headdim=16, ssm_ngroups=1,
                       attn_every=2, vocab=300)
        counts = gemm_shape_counts(cfg, 4)
        di = cfg.d_inner
        n_in = 2 * di + 2 * cfg.ssm_ngroups * cfg.ssm_state \
            + di // cfg.ssm_headdim
        assert (4, n_in, cfg.d_model) in counts
        assert (4, 2 * di, cfg.d_model) not in counts

    def test_gemm_shape_counts_consistent_with_shapes(self, served):
        cfg, _, _ = served
        counts = gemm_shape_counts(cfg, 16)
        assert sorted(counts) == gemm_shapes(cfg, 16)
        # one decode step: Q, 2x KV, O per layer; up+gate, down per layer;
        # one LM head
        d, hd = cfg.d_model, cfg.hd
        assert counts[(16, cfg.vocab, d)] == 1
        assert counts[(16, cfg.kv_heads * hd, d)] == 2 * cfg.n_layers
        assert counts[(16, cfg.d_ff, d)] == 2 * cfg.n_layers

    def test_serving_fleet_covers_slot_prefill_buckets(self, served):
        from repro.kernels import ops

        cfg, _, _ = served
        fleet = set(ops.serving_gemm_fleet(cfg, max_batch=4, max_len=64))
        assert set(gemm_shapes(cfg, 4)) <= fleet          # decode
        # batched prefill: head GEMM sized to rows actually unembedded
        assert set(gemm_shape_counts(cfg, 4 * 64, head_tokens=4)) <= fleet
        for b in (8, 16, 32, 64):                         # slot buckets
            assert set(gemm_shape_counts(cfg, b, head_tokens=1)) <= fleet
        # prefill never unembeds every position, so the full-row head
        # shape must NOT be pre-tuned (it is never traced)
        assert (4 * 64, cfg.vocab, cfg.d_model) not in fleet
        no_slots = set(ops.serving_gemm_fleet(
            cfg, max_batch=4, max_len=64, include_slot_prefill=False))
        assert not (set(gemm_shape_counts(cfg, 8, head_tokens=1))
                    <= no_slots)

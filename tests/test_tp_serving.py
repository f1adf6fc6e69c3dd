"""Multi-device (tensor-parallel) serving tests.

The sharding contract is *bit parity*: a ServingEngine built with
``tp > 1`` — gather-mode explicit collectives, head/expert-sharded
params and caches, compute-overlapped row-parallel all-gathers — must
produce greedy token streams identical to the tp=1 engine for every
served family, while reporting collective wire/overlap telemetry and a
fleet (n_chips x) energy estimate.

These run in a subprocess with virtual host devices
(``--xla_force_host_platform_device_count``) so the main test process
keeps its single-device view (same idiom as test_sharding.py).
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.setdefault("JAX_PLATFORMS", "cpu")
    src = textwrap.dedent(_PRELUDE) + textwrap.dedent(code)
    out = subprocess.run([sys.executable, "-c", src],
                         capture_output=True, text=True, env=env,
                         timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# one engine-build helper shared by every subprocess snippet
_PRELUDE = """
    import jax
    import numpy as np
    from repro.models.config import ModelConfig
    from repro.models.registry import get_model
    from repro.serving.engine import Request, ServingEngine

    BASE = dict(name="tp-test", n_layers=2, d_model=64, n_heads=4,
                n_kv_heads=2, vocab=128, param_dtype="float32",
                activation_dtype="float32", remat=False)
    FAMILY_KW = {
        "dense": dict(d_ff=128),
        "moe": dict(d_ff=0, n_experts=4, top_k=2, d_ff_expert=64,
                    capacity_factor=16.0),
        "mla_moe": dict(d_ff=128, n_experts=4, top_k=2, d_ff_expert=64,
                        capacity_factor=16.0, n_shared_experts=1,
                        kv_lora_rank=16, rope_head_dim=8),
        "mamba1": dict(d_ff=0, ssm_state=8, expand=2, d_conv=4),
        "mamba2": dict(d_ff=0, ssm_state=8, expand=2, d_conv=4,
                       ssm_headdim=16, ssm_ngroups=1),
        "hybrid": dict(d_ff=128, ssm_state=8, expand=2, d_conv=4,
                       ssm_headdim=16, ssm_ngroups=1, attn_every=2),
        "encdec": dict(d_ff=128, n_encoder_layers=2, gated_mlp=False),
        "vlm": dict(d_ff=128, qkv_bias=True, mrope=True,
                    mrope_sections=(4, 2, 2)),
    }
    LM_FAMILIES = ("dense", "hybrid", "mamba1", "mamba2", "mla_moe", "moe")

    def build(kind, **over):
        kw = dict(BASE, kind=kind, **FAMILY_KW[kind])
        kw.update(over)
        cfg = ModelConfig(**kw)
        model = get_model(cfg)
        params = model.init(jax.random.key(0), cfg)
        return cfg, model, params

    def extras(cfg, uid):
        rng = np.random.default_rng(900 + uid)
        if cfg.kind == "encdec":
            t = 5 + 2 * (uid % 3)
            return {"src_embeds": rng.standard_normal(
                (t, cfg.d_model)).astype(np.float32)}
        if cfg.kind == "vlm":
            grid = [(4, 4), (2, 3), None][uid % 3]
            if grid is None:
                return None
            gh, gw = grid
            return {"patch_embeds": rng.standard_normal(
                (gh * gw, cfg.d_model)).astype(np.float32),
                "grid_hw": grid}
        return None

    def reqs(cfg, specs):
        out = []
        for uid, (seed, n, mnt) in enumerate(specs):
            p = np.random.default_rng(seed).integers(
                0, cfg.vocab, n).astype(np.int32)
            out.append(Request(uid=uid, prompt=p, max_new_tokens=mnt,
                               extras=extras(cfg, uid)))
        return out

    SPECS = [(0, 11, 10), (1, 7, 8), (2, 19, 6), (3, 5, 12), (4, 13, 4)]

    def serve(cfg, model, params, tp, **kw):
        eng = ServingEngine(model, params, cfg, max_batch=2, max_len=64,
                            tp=tp, **kw)
        for r in reqs(cfg, SPECS):
            eng.submit(r)
        res = {r.uid: r.tokens.tolist() for r in eng.run_until_empty()}
        return eng, res, eng.report()
"""


class TestTpBitParity:
    def test_all_families_tp2_streams_identical(self):
        """Every continuously-served family: tp=2 greedy streams ==
        tp=1, with nonzero wire time and overlap telemetry at tp=2."""
        stdout = _run_sub("""
            for kind in LM_FAMILIES:
                _, r1, _ = serve(*build(kind), tp=1)
                _, r2, rep = serve(*build(kind), tp=2)
                assert r1 == r2, (kind, r1, r2)
                assert rep["tp"] == 2
                assert rep["collective_wire_s"] > 0.0, kind
                assert 0.0 < rep["overlap_factor"] < 1.0, kind
                assert rep["model_s"] > 0.0, kind
                print("PARITY", kind)
            print("OK")
        """)
        assert "OK" in stdout
        for kind in ("dense", "moe", "mla_moe", "mamba1", "mamba2",
                     "hybrid"):
            assert f"PARITY {kind}" in stdout

    def test_admit_families_tp2_streams_identical(self):
        """encdec and vlm under tp=2: the admission pass (encoder +
        cross-KV projection, patch prefix) runs through the gather-mode
        sharded params, and greedy streams stay bit-identical to tp=1.
        Cross-KV and patch admission use tp_column/tp_row, so the tp=2
        contraction order matches tp=1 exactly."""
        stdout = _run_sub("""
            for kind in ("encdec", "vlm"):
                _, r1, _ = serve(*build(kind), tp=1)
                _, r2, rep = serve(*build(kind), tp=2)
                assert r1 == r2, (kind, r1, r2)
                assert rep["collective_wire_s"] > 0.0, kind
                print("PARITY", kind)
            print("OK")
        """)
        assert "OK" in stdout
        for kind in ("encdec", "vlm"):
            assert f"PARITY {kind}" in stdout

    def test_dense_tp4_streams_identical(self):
        """tp=4 over a 4-way-divisible head layout: parity plus sharded
        param/cache placement (params column-sharded on the mesh)."""
        stdout = _run_sub("""
            over = dict(n_heads=8, n_kv_heads=4)
            _, r1, _ = serve(*build("dense", **over), tp=1)
            eng, r4, rep = serve(*build("dense", **over), tp=4)
            assert r1 == r4
            assert rep["tp"] == 4
            spec = eng.params["blocks"]["attn"]["wq"].sharding.spec
            assert "model" in [ax for ax in spec if ax is not None]
            print("OK")
        """)
        assert "OK" in stdout

    def test_tp_weight_reads_staged(self):
        """Under explicit TP each weight is sliced out of its stack before
        the shard_map, so the Pallas calls count it as staged; the same
        engine at tp=1 reads every weight in place."""
        stdout = _run_sub("""
            from repro.kernels import ops
            from repro.kernels.tiled_matmul import BlockConfig

            ops._tuned_config = lambda *_: BlockConfig(16, 128, 128)
            ops.force_mode("pallas_interpret")
            for tp in (1, 2):
                cfg, model, params = build("dense")
                eng = ServingEngine(model, params, cfg, max_batch=2,
                                    max_len=64, tp=tp)
                eng.submit(reqs(cfg, SPECS[:1])[0])
                eng.run_until_empty()
                reads = eng.report()["weight_reads"]
                print("READS", tp, reads["in_place"], reads["staged"])
        """)
        got = {int(tp): (int(i), int(s)) for _, tp, i, s in (
            ln.split() for ln in stdout.splitlines()
            if ln.startswith("READS"))}
        assert got[1][0] > 0 and got[1][1] == 0
        assert got[2][0] == 0 and got[2][1] > 0

    def test_fleet_energy_scales_chips(self):
        """The tp report prices the fleet: per-step estimates carry
        n_chips=tp and J/token strictly above the single-chip run (same
        tokens, tp chips burning a shorter step)."""
        stdout = _run_sub("""
            _, _, rep1 = serve(*build("dense"), tp=1)
            eng, _, rep2 = serve(*build("dense"), tp=2)
            assert rep2["j_per_token"] > rep1["j_per_token"]
            est = eng._step_energy(("decode", eng.max_batch),
                                   eng.max_batch,
                                   batch_rows=eng.max_batch)
            assert est.n_chips == 2
            assert est.collective_s > 0.0
            print("OK")
        """)
        assert "OK" in stdout


class TestTpPagedKv:
    def test_sharded_pool_parity_and_refcount_hygiene(self):
        """Paged KV under tp=2: the shared pool's k/v pages shard on the
        head axis, streams match the tp=1 dense layout, and after two
        full drains every non-registry page ref has been released (no
        leak from the sharded pool threading)."""
        stdout = _run_sub("""
            kw = dict(admission="chunked", chunk_tokens=16,
                      kv_layout="paged", page_size=16)
            _, r_dense, _ = serve(*build("dense"), tp=1)
            eng, r_paged, rep = serve(*build("dense"), tp=2, **kw)
            assert r_dense == r_paged
            spec = eng._pool["k_pages"].sharding.spec
            assert "model" in [ax for ax in spec if ax is not None]
            alloc = eng._allocator
            use1 = alloc.in_use
            held1 = int((alloc.refs > 0).sum())
            # second drain over the same prompts: prefix registry may
            # hold pages, but repeated serving must not accumulate refs
            for r in reqs(eng.cfg, SPECS):
                r.uid += 100
                eng.submit(r)
            r_again = {r.uid - 100: r.tokens.tolist()
                       for r in eng.run_until_empty()}
            assert r_again == r_paged
            assert alloc.in_use == use1
            assert int((alloc.refs > 0).sum()) == held1
            assert (alloc.refs >= 0).all()
            print("OK")
        """)
        assert "OK" in stdout


class TestGrainSharded:
    def test_wider_grain_keeps_tp_parity(self):
        """ssm_serve_grain=32 composes with tp=2: chunked mamba2 streams
        still match the tp=1 engine at the same grain."""
        stdout = _run_sub("""
            kw = dict(admission="chunked", chunk_tokens=32,
                      ssm_serve_grain=32)
            _, r1, _ = serve(*build("mamba2"), tp=1, **kw)
            _, r2, _ = serve(*build("mamba2"), tp=2, **kw)
            assert r1 == r2
            print("OK")
        """)
        assert "OK" in stdout

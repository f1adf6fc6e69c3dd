"""Serving example: continuous batching with chunked admission prefill
and per-request energy accounting over a small dense LM (random weights —
the point is the serving machinery: prompts chunk-prefill through the
decode loop in bucketed lane calls, finished rows splice into decode
slots, finished slots retire mid-decode and refill, telemetry + J/token
report).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""

import time

import numpy as np

import jax

from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import ModelConfig
from repro.models.registry import get_model
from repro.serving.engine import Request, ServingEngine


def main():
    enable_compile_cache()
    cfg = ModelConfig(
        name="serve-demo", kind="dense", n_layers=4, d_model=256, n_heads=8,
        n_kv_heads=4, d_ff=1024, vocab=4096, param_dtype="float32",
        activation_dtype="float32", remat=False,
    )
    model = get_model(cfg)
    params = model.init(jax.random.key(0), cfg)

    def submit_all(engine, n_requests=10, seed=0):
        rng = np.random.default_rng(seed)
        for uid in range(n_requests):
            # one long prompt up front — the shape that used to stall
            # every other request behind its serialized prefill
            n = 96 if uid == 0 else int(rng.integers(4, 24))
            prompt = rng.integers(0, cfg.vocab, n)
            engine.submit(Request(
                uid=uid, prompt=prompt.astype(np.int32),
                # mixed budgets — the shape where continuous batching wins
                max_new_tokens=int(rng.choice([4, 8, 32]))))

    # continuous mode (the default for every LM family, SSM included):
    # prompts chunk-prefill through the decode loop (chunk_tokens per
    # step, queued admissions batched per call), finished slots retire
    # mid-decode and refill from the queue
    engine = ServingEngine(model, params, cfg, max_batch=4, max_len=128,
                           chunk_tokens=32)
    submit_all(engine)
    t0 = time.perf_counter()
    results = engine.run_until_empty()
    dt = time.perf_counter() - t0
    for r in sorted(results, key=lambda r: r.uid)[:4]:
        print(f"req {r.uid}: prompt_len={r.prompt_len} "
              f"n_tokens={r.n_tokens} steps={r.steps} "
              f"ttft={r.ttft_s * 1e3:.0f}ms "
              f"energy={r.energy_j * 1e3:.2f}mJ -> {r.tokens[:6]}...")
    rep = engine.report()
    print(f"continuous: {rep['requests']} requests, "
          f"{rep['generated_tokens']} tokens in {dt:.2f}s | "
          f"occupancy={rep['slot_occupancy']:.2f} "
          f"J/token={rep['j_per_token']:.2e} "
          f"slot_steps={rep['slot_steps']:.0f} "
          f"chunk_steps={rep['chunk_steps']}")

    # same workload through the legacy wave loop: identical greedy streams,
    # strictly more executed decode-step*slots ("Racing to Idle")
    wave = ServingEngine(model, params, cfg, max_batch=4, max_len=128,
                         mode="wave")
    submit_all(wave)
    wave_results = {r.uid: r for r in wave.run_until_empty()}
    for r in results:
        np.testing.assert_array_equal(r.tokens, wave_results[r.uid].tokens)
    wrep = wave.report()
    print(f"wave:       identical streams | "
          f"J/token={wrep['j_per_token']:.2e} "
          f"slot_steps={wrep['slot_steps']:.0f}")
    assert len(results) == 10
    print("serve_lm OK")


if __name__ == "__main__":
    main()

"""Serving gate: closed compile set + exactness under live traffic (CPU).

One-command proof of the serving subsystem's two contracts, cheap enough
for every gate run:

1. **InferenceEngine** — export a small model, warm two buckets, fire
   mixed-shape traffic; the executable count must stay at exactly
   ``len(buckets)`` and every padded answer must match the direct
   predictor bit-for-bit (after unpadding).
2. **GenerationEngine** — batched ragged KV-cache greedy decode must be
   token-identical to the uncached full-recompute forward, with exactly
   ``len(prompt_buckets) + 1`` compiles.

Prints one JSON line; exit 0 iff both gates hold.
"""
import json
import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu import nn  # noqa: E402
from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM  # noqa: E402
from paddle_tpu.serving import (  # noqa: E402
    Bucket,
    GenerationEngine,
    InferenceEngine,
)


class _Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(8, 4)

    def forward(self, x):
        return self.fc(x)


def gate_inference(tmp):
    pt.seed(7)
    net = _Net()
    prefix = os.path.join(tmp, "m")
    pt.inference.save_inference_model(
        prefix, net, [pt.static.InputSpec([None, None, 8], "float32")])
    with InferenceEngine(prefix, [Bucket(((4, 8),)), Bucket(((16, 8),))],
                         max_batch_size=4, max_queue_delay_ms=2.0) as eng:
        eng.warmup()
        rng = np.random.RandomState(0)
        xs = [rng.randn(n, 8).astype("float32")
              for n in (1, 3, 4, 2, 9, 16, 3, 11, 4, 7)]
        futs = [eng.submit([x]) for x in xs]
        ok = True
        for x, f in zip(xs, futs):
            got = f.result(120)[0]
            want = np.asarray(net(x[None]))[0]
            ok &= got.shape == want.shape and np.allclose(got, want,
                                                          atol=1e-5)
        st = eng.stats()
        closed = st["compile_count"] == 2 and st["bucket_misses"] == 0
        return {"exact": bool(ok), "closed_compile_set": bool(closed),
                "compile_count": st["compile_count"],
                "batches": st["batches"], "completed": st["completed"],
                "p99_ms": round(st["p99_ms"], 2)}


def gate_generation():
    import jax.numpy as jnp

    pt.seed(11)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=4,
                    max_position=64, dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()

    def ref(prompt, n):
        ids, outs = list(map(int, prompt)), []
        for _ in range(n):
            logits = np.asarray(model(jnp.asarray([ids], jnp.int32)))[0]
            outs.append(int(np.argmax(logits[-1])))
            ids.append(outs[-1])
        return outs

    with GenerationEngine(model, prompt_buckets=[8, 16], batch_size=2,
                          max_queue_delay_ms=2.0) as eng:
        eng.warmup()
        prompts = [np.arange(5) % 97, (np.arange(7) * 3) % 97,
                   (np.arange(11) * 5 + 2) % 97]
        futs = [eng.submit(p, max_new_tokens=5) for p in prompts]
        gens = [f.result(300) for f in futs]
        identical = all(g.tolist() == ref(p, 5)
                        for p, g in zip(prompts, gens))
        st = eng.stats()
        # per-bucket admission + the verify step + its [B, 1] fast trace
        # + the page copy (the default engine speculates)
        expected = len([8, 16]) + 3
        return {"token_identical": bool(identical),
                "closed_compile_set": st["compile_count"] == expected,
                "compile_count": st["compile_count"],
                "tokens": st["tokens"],
                "tokens_per_s": round(st["tokens_per_s"], 1)}


def main():
    t0 = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        inf = gate_inference(tmp)
        gen = gate_generation()
    passed = (inf["exact"] and inf["closed_compile_set"]
              and gen["token_identical"] and gen["closed_compile_set"])
    print(json.dumps({"pass": bool(passed), "inference": inf,
                      "generation": gen,
                      "seconds": round(time.time() - t0, 1)}))
    return 0 if passed else 1


if __name__ == "__main__":
    sys.exit(main())

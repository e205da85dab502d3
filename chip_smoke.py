"""chip_smoke.py — the quickest proof that the framework still starts on a TPU.

One process, no arguments, no network, weights and data from a fixed seed.
It drives the framework's two main paths through the entry points a user
would call, at the published width of models the repo ships, on ONE chip:

* **train**  — BERT-base (768 x 12 layers x 12 heads, vocab 30522, seq 128,
  bf16 params + f32 master, AdamW) through ``fluid.Program`` ->
  ``Executor.run_steps``: two windows of chained steps on one fixed batch
  (loss finite, lower after than before), then the first-step loss of the
  same seeded program with ``FLAGS_fused_epilogues=0`` — the fused Pallas
  epilogues against plain XLA, within a bf16 tolerance.
* **resnet** — ResNet-50 (NHWC, space-to-depth stem, bf16, batch 128,
  Momentum) through the same ``run_steps`` path: one warm window and one
  more, loss finite, the conv1x1+BN kernels dispatched.
* **serve**  — GPT-2-small (768 x 12 x 12, vocab 50304, max_position 1024)
  behind ``serving.GenerationEngine``:
  ``warmup()``, eight concurrent ragged requests, every token checked
  against the uncached greedy forward (teacher-forced; a flip is allowed
  only where the reference's top-2 logit margin is within ``MARGIN_K`` x
  the measured precision noise), zero XLA compiles after warm-up, the
  paged-decode kernel dispatched.  A second, ``quantized="int8"`` engine
  answers two requests so ``quantized_matmul`` and the int8 page walk run.

``python chip_smoke.py --chips 4`` runs ONLY the mesh path and what it is
compared with: GPT at gpt_small width, ``Model.train_batch`` steps under
``fleet.init`` with data=2 x model=2 and data=2 x sharding=2 on four
chips, against the same seeded steps on a one-device mesh in this process.

Output: one JSON object per phase, then — as the LAST line —
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check exits non-zero with ``"ok": false``.  Without a TPU the
script fails at once: it never sets the JAX platform and never runs a
phase on the CPU.  Timings printed here are smoke timings (one cold run,
one warm run), not benchmark numbers.
"""
import argparse
import gc
import json
import sys
import time

import numpy as np

SEED = 0
#: |fused - unfused| first-step BERT loss, relative.  The unfused path sums
#: a bf16 log-softmax over 30522 classes in bf16 (spacing 0.0625 at the
#: loss's magnitude of ~11); the fused kernel accumulates in f32.
FUSED_LOSS_RTOL = 1.5e-2
#: a served token may differ from the reference argmax only where the
#: reference's top-2 logit margin is <= MARGIN_K x the measured logit
#: perturbation (default-precision vs highest-precision forward for the
#: float engine; int8-weight vs float forward for the int8 engine) — the
#: accounting tools/quant_smoke.py uses.
MARGIN_K = 4.0
#: ... and overall agreement with the reference argmax has a floor (the one
#: tools/quant_smoke.py holds its quantized engines to)
AGREE_FLOOR = 0.85
#: 4-chip vs 1-device loss, relative, per step (f32 params, dropout off:
#: only the reduction order differs between the layouts)
MESH_LOSS_RTOL = 5e-3


class SmokeFailure(Exception):
    """A check of a phase did not hold."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def emit(obj):
    print(json.dumps(obj), flush=True)


class CompileMonitor:
    """Counts XLA compile requests and persistent-cache hits through
    ``jax.monitoring`` (the ground truth tools/gen_smoke.py uses: it sees
    the placement-specialised recompiles a trace counter cannot)."""

    def __init__(self):
        import jax

        self.requests = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _on_event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.requests, self.cache_hits, self.cache_misses)

    def since(self, snap):
        r, h, m = self.snapshot()
        return {"xla_compiles": r - snap[0], "cache_hits": h - snap[1],
                "cache_misses": m - snap[2]}


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def forward_text(exe, main, loss, feeds):
    """A ``fluid.Program``'s forward, feeds to loss, as JAX lowers it for
    this backend (StableHLO text; traced under the gates the run's own
    trace saw, nothing compiled or run)."""
    import jax

    def forward(params, buffers, feeds):
        env, _ = exe._execute(main, params, buffers, feeds, True,
                              rng=jax.random.PRNGKey(SEED))
        return env[loss.name]

    return jax.jit(forward).lower(dict(main.scope), dict(main.buffers),
                                  feeds).as_text()


def check_dispatched(text, at_least, *names):
    """The program's own text (lowered or compiled) holds its kernels: at
    least ``at_least`` Mosaic custom calls, and each of ``names`` (a
    ``pallas_call``'s ``name=``) among them, as ``tests/test_tpu_compile.py``
    reads a program.  A gate that was shut, or a stand-in that ran in a
    kernel's place, leaves no call."""
    calls = text.count("tpu_custom_call")
    check(calls >= at_least and all(n in text for n in names),
          f"the program holds {calls} Mosaic kernel calls, wanted "
          f"{at_least} or more with {names} among them")
    return {"tpu_custom_calls": calls}


# -- device -------------------------------------------------------------------
def device_phase(need):
    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(dev["platform"] == "tpu",
          f"no TPU: jax.devices() reports platform {dev['platform']!r}")
    check(len(devs) >= need, f"need {need} chips, have {len(devs)}")
    return dev


# -- train: BERT-base through Executor.run_steps ------------------------------
def _bert_program(batch, seq, max_pred, cfg):
    """BERT-base masked-LM + NSP pretraining as a ``fluid.Program``."""
    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu import optimizer as popt
    from paddle_tpu.models import BertForPretraining
    from paddle_tpu.static.builders import layer_op
    from paddle_tpu.static.graph import record_call

    paddle.seed(SEED)
    net = BertForPretraining(cfg).astype("bfloat16")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids_v = fluid.data("input_ids", [batch, seq], "int32")
        tt_v = fluid.data("token_type_ids", [batch, seq], "int32")
        am_v = fluid.data("attention_mask", [batch, seq], "int32")
        mp_v = fluid.data("masked_positions", [batch, max_pred], "int32")
        mlm_y = fluid.data("mlm_labels", [batch, max_pred], "int32")
        nsp_y = fluid.data("nsp_labels", [batch, 1], "int32")
        mlm_logits, nsp_logits = layer_op(
            net, ids_v, prefix="bert", extra_args=(tt_v, am_v, mp_v))
        loss = record_call(net.loss, mlm_logits, nsp_logits, mlm_y, nsp_y,
                           prefix="bert_loss")
        popt.AdamW(learning_rate=1e-4, weight_decay=0.01,
                   multi_precision=True).minimize(loss)
    rng = np.random.RandomState(SEED)
    ids = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    positions = np.stack([
        np.sort(rng.choice(seq, max_pred, replace=False))
        for _ in range(batch)]).astype(np.int32)
    feeds = {
        "input_ids": ids,
        "token_type_ids": (rng.uniform(size=(batch, seq)) < 0.5)
        .astype(np.int32),
        "attention_mask": np.ones((batch, seq), np.int32),
        "masked_positions": positions,
        "mlm_labels": np.take_along_axis(ids, positions, axis=1),
        "nsp_labels": rng.randint(0, 2, size=(batch, 1)).astype(np.int32),
    }
    exe = fluid.Executor()
    exe.run(startup)
    return exe, main, loss, feeds


def _window(exe, main, loss, feeds, n_steps):
    """One dispatch of ``n_steps`` chained optimizer steps; returns the
    per-step losses and the wall seconds (the D2H read truly waits)."""
    t0 = time.perf_counter()
    out, = exe.run_steps(main, feed=feeds, fetch_list=[loss],
                         iterations=n_steps, fetch_every=1,
                         constant_feeds=tuple(feeds))
    losses = np.asarray(out, np.float32).reshape(-1)
    return losses, time.perf_counter() - t0


def train_phase(mon, batch=256, seq=128, max_pred=20, n_steps=4, cfg=None):
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.models import bert_base

    cfg = cfg or bert_base()
    snap = mon.snapshot()
    exe, main, loss, feeds = _bert_program(batch, seq, max_pred, cfg)
    w1, cold_s = _window(exe, main, loss, feeds, n_steps)
    w2, steady_s = _window(exe, main, loss, feeds, n_steps)
    check(np.isfinite(w1).all() and np.isfinite(w2).all(),
          f"non-finite BERT loss: {w1.tolist()} {w2.tolist()}")
    check(w2[-1] < w1[0],
          f"BERT loss did not fall on a fixed batch: {w1[0]} -> {w2[-1]}")
    # layernorm_residual twice a layer and softmax_xent over the MLM head
    counters = check_dispatched(forward_text(exe, main, loss, feeds),
                                2 * cfg.num_layers + 1)
    del exe, main, loss
    gc.collect()

    # the same seeded program on plain XLA: first-step loss must agree
    set_flags({"fused_epilogues": False})
    try:
        exe, main, loss, feeds = _bert_program(batch, seq, max_pred, cfg)
        u1, unfused_s = _window(exe, main, loss, feeds, 1)
    finally:
        set_flags({"fused_epilogues": True})
    del exe, main, loss
    gc.collect()
    rel = abs(float(w1[0]) - float(u1[0])) / abs(float(u1[0]))
    check(np.isfinite(u1).all() and rel <= FUSED_LOSS_RTOL,
          f"fused first-step loss {w1[0]} vs unfused {u1[0]}: rel {rel:.3g} "
          f"> {FUSED_LOSS_RTOL}")
    return {
        "phase": "train", "model": "bert_base", "batch": batch, "seq": seq,
        "layers": cfg.num_layers, "hidden": cfg.hidden_size,
        "vocab": cfg.vocab_size, "steps_per_window": n_steps,
        "loss_first": float(w1[0]), "loss_last": float(w2[-1]),
        "loss_first_unfused": float(u1[0]), "fused_vs_unfused_rel": rel,
        "fused_vs_unfused_rtol": FUSED_LOSS_RTOL, "kernels": counters,
        "smoke_cold_window_s": round(cold_s, 2),
        "smoke_steady_window_s": round(steady_s, 3),
        "smoke_unfused_cold_window_s": round(unfused_s, 2),
        "peak_bytes_in_use": peak_bytes(), **mon.since(snap),
    }


# -- resnet: ResNet-50 through Executor.run_steps -----------------------------
def resnet_phase(mon, batch=128, image=224, n_steps=3):
    import jax.numpy as jnp
    import ml_dtypes

    import paddle_tpu as paddle
    import paddle_tpu.fluid as fluid
    from paddle_tpu import optimizer as popt
    from paddle_tpu.static.builders import layer_op
    from paddle_tpu.static.graph import record_call
    from paddle_tpu.vision.models import resnet50

    snap = mon.snapshot()
    paddle.seed(SEED)
    net = resnet50(data_format="NHWC",
                   stem_space_to_depth=True).astype("bfloat16")
    loss_layer = paddle.nn.CrossEntropyLoss()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.data("image", [batch, image, image, 3], "bfloat16")
        label = fluid.data("label", [batch, 1], "int32")
        logits = layer_op(net, img, prefix="resnet50")
        loss = record_call(
            lambda o, y: loss_layer(o.astype(jnp.float32), y),
            logits, label, prefix="xent")
        popt.Momentum(learning_rate=0.1, momentum=0.9, multi_precision=True,
                      weight_decay=1e-4).minimize(loss)
    rng = np.random.RandomState(SEED)
    feeds = {"image": rng.uniform(-1, 1, (batch, image, image, 3))
             .astype(ml_dtypes.bfloat16),
             "label": rng.randint(0, 1000, (batch, 1)).astype(np.int32)}
    exe = fluid.Executor()
    exe.run(startup)
    w1, cold_s = _window(exe, main, loss, feeds, n_steps)
    w2, steady_s = _window(exe, main, loss, feeds, n_steps)
    check(np.isfinite(w1).all() and np.isfinite(w2).all(),
          f"non-finite ResNet-50 loss: {w1.tolist()} {w2.tolist()}")
    # conv1x1_bn_stats and bn_apply_relu in a bottleneck's tail
    counters = check_dispatched(forward_text(exe, main, loss, feeds), 2)
    del exe, main, loss, net
    gc.collect()
    return {
        "phase": "resnet", "model": "resnet50", "batch": batch,
        "image": image, "steps_per_window": n_steps,
        "loss_first": float(w1[0]), "loss_last": float(w2[-1]),
        "kernels": counters, "smoke_cold_window_s": round(cold_s, 2),
        "smoke_steady_window_s": round(steady_s, 3),
        "peak_bytes_in_use": peak_bytes(), **mon.since(snap),
    }


# -- serve: GPT-2-small behind the paged continuous engine --------------------
def _teacher_forced_logits(model, histories, starts, counts, precision):
    """Uncached forward of ``model`` over each whole history (prompt +
    served tokens, right-padded — causal, so padding is inert) on plain
    XLA; returns per request the ``[count, V]`` float32 logits that predict
    its served tokens."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.nn.layer_base import functional_call

    L = -(-max(len(h) for h in histories) // 128) * 128
    ids = np.zeros((len(histories), L), np.int32)
    for r, h in enumerate(histories):
        ids[r, :len(h)] = h
    n_max = max(counts)
    rows = np.stack([np.minimum(s + np.arange(n_max), L - 1)
                     for s in starts]).astype(np.int32)  # [R, n_max]

    def fwd(params, buffers, ids, rows):
        logits = functional_call(model, params, ids, buffers=buffers,
                                 training=False)
        return jnp.take_along_axis(
            logits.astype(jnp.float32), rows[:, :, None], axis=1)

    set_flags({"fused_epilogues": False})  # the reference is plain XLA
    try:
        with jax.default_matmul_precision(precision):
            out = jax.jit(fwd)(model.param_pytree(), model.buffer_pytree(),
                               jnp.asarray(ids), jnp.asarray(rows))
    finally:
        set_flags({"fused_epilogues": True})
    out = np.asarray(out)
    return [out[r, :n] for r, n in enumerate(counts)]


def _margin_account(ref_logits, noisy_logits, served, need_clear):
    """Margin-accounted agreement of served tokens with the reference:
    ``tau = MARGIN_K x max|noisy - ref|`` is the noise floor; a served
    token may miss the reference argmax only where the reference's top-2
    margin is <= tau, and overall agreement stays above ``AGREE_FLOOR``.
    ``need_clear``: some token must clear the bar (the float engine, whose
    noise is small against random weights' margins; int8's is not, and
    its floor is what keeps the check from being vacuous)."""
    delta = max(float(np.max(np.abs(a - b)))
                for a, b in zip(ref_logits, noisy_logits))
    tau = MARGIN_K * delta
    total = agree = clear = clear_flips = 0
    for logits, toks in zip(ref_logits, served):
        top2 = np.partition(logits, -2, axis=-1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        ok = np.argmax(logits, axis=-1) == np.asarray(toks)
        total += len(toks)
        agree += int(ok.sum())
        clear += int((margin > tau).sum())
        clear_flips += int(((margin > tau) & ~ok).sum())
    acct = {"tokens": total, "agreement": round(agree / total, 4),
            "logit_noise": delta, "margin_tau": tau, "margin_k": MARGIN_K,
            "clear_margin_tokens": clear, "clear_margin_flips": clear_flips,
            "agreement_floor": AGREE_FLOOR}
    check(clear_flips == 0 and agree / total >= AGREE_FLOOR
          and (clear > 0 or not need_clear),
          f"served tokens disagree with the uncached greedy forward: {acct}")
    return acct


def _serve(model, prompts, new_tokens, mon, **engine_kw):
    """Warm an engine, answer every request concurrently, count compiles
    after warm-up; returns (served tokens, timings/counters, the decode
    step's compiled text)."""
    from paddle_tpu.resilience import retry
    from paddle_tpu.serving import GenerationEngine

    t0 = time.perf_counter()
    with GenerationEngine(model, **engine_kw) as eng:
        compiled = eng.warmup()
        warm_s = time.perf_counter() - t0
        snap = mon.snapshot()
        t1 = time.perf_counter()
        futs = [eng.submit(p, n) for p, n in zip(prompts, new_tokens)]
        served = [np.asarray(f.result(600)).tolist() for f in futs]
        wall_s = time.perf_counter() - t1
        post = mon.since(snap)
        compile_count = eng.compile_count
        step_text = eng.compiled_programs()["step"]
    for toks, n in zip(served, new_tokens):
        check(len(toks) == n, f"request answered {len(toks)} of {n} tokens")
    check(post["xla_compiles"] == 0 and compile_count == compiled,
          f"XLA compiled after warm-up: {post}, executables "
          f"{compiled} -> {compile_count}")
    retries = sum(c["retries"] for c in retry.stats().values())
    check(retries == 0, f"{retries} silent transient retries")
    return served, {"warmup_executables": compiled,
                    "smoke_cold_warmup_s": round(warm_s, 2),
                    "smoke_steady_serve_s": round(wall_s, 3),
                    "post_warmup_xla_compiles": post["xla_compiles"]
                    }, step_text


def serve_phase(mon, cfg=None, buckets=(64, 256, 512), batch_size=4,
                prompt_lens=(16, 37, 64, 101, 180, 256, 333, 400),
                new_tokens=(32, 48, 64, 40, 56, 32, 64, 48),
                int8_bucket=64, int8_prompt_lens=(24, 50),
                int8_new_tokens=(32, 32)):
    import copy

    import paddle_tpu as paddle
    from paddle_tpu import slim
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = cfg or GPTConfig()
    snap = mon.snapshot()
    paddle.seed(SEED)
    model = GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(SEED)

    def make(lens):
        return [rng.randint(1, cfg.vocab_size, size=n).astype(np.int32)
                for n in lens]

    def histories(prompts, served):
        hist = [list(map(int, p)) + t for p, t in zip(prompts, served)]
        starts = [len(p) - 1 for p in prompts]
        return hist, starts, [len(t) for t in served]

    # float engine: default kv_page_size / speculative_k
    prompts = make(prompt_lens)
    served, info, step = _serve(model, prompts, list(new_tokens), mon,
                                prompt_buckets=list(buckets),
                                batch_size=batch_size, name="chip-smoke")
    # a layer's page walk
    counters = check_dispatched(step, cfg.num_layers, "paged_decode")
    hist, starts, counts = histories(prompts, served)
    ref = _teacher_forced_logits(model, hist, starts, counts, "highest")
    dflt = _teacher_forced_logits(model, hist, starts, counts, "default")
    acct = _margin_account(ref, dflt, served, need_clear=True)

    # int8 engine: quantized weights + int8 KV pages, two requests
    q_prompts = make(int8_prompt_lens)
    q_served, q_info, q_step = _serve(
        model, q_prompts, list(int8_new_tokens), mon,
        prompt_buckets=[int8_bucket], batch_size=2, quantized="int8",
        name="chip-smoke-int8")
    # the int8 page walk, and quantized_matmul (it has no name of its own)
    # for a layer's linears beside it
    q_counters = check_dispatched(q_step, 2 * cfg.num_layers, "paged_decode")
    for toks in q_served:
        check(all(0 <= t < cfg.vocab_size for t in toks),
              "int8 engine served a token outside the vocabulary")
    hist, starts, counts = histories(q_prompts, q_served)
    q_ref = _teacher_forced_logits(model, hist, starts, counts, "highest")
    qm = copy.deepcopy(model)
    slim.quantize_weights(qm, "int8")
    q_noisy = _teacher_forced_logits(qm, hist, starts, counts, "default")
    q_acct = _margin_account(q_ref, q_noisy, q_served, need_clear=False)
    del qm, model
    gc.collect()
    return {
        "phase": "serve", "model": "gpt_small", "layers": cfg.num_layers,
        "hidden": cfg.hidden_size, "vocab": cfg.vocab_size,
        "max_position": cfg.max_position, "batch_size": batch_size,
        "prompt_buckets": list(buckets), "prompt_lens": list(prompt_lens),
        "new_tokens": list(new_tokens), "requests_answered": len(served),
        "token_check": acct, **info, "kernels": counters,
        "int8": {"requests_answered": len(q_served), "token_check": q_acct,
                 **q_info, "kernels": q_counters},
        "peak_bytes_in_use": peak_bytes(), **mon.since(snap),
    }


# -- --chips 4: the mesh path against a one-device mesh -----------------------
def _mesh_losses(devices, strategy_kw, cfg_kw, ids, n_steps, prove_spread):
    """Seeded GPT, ``fleet.init`` over ``devices``, ``n_steps`` of
    ``Model.train_batch``; returns (losses, spread proof or None)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as popt
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    gc.collect()
    before = [d.memory_stats()["bytes_in_use"] for d in devices]
    fleet._initialized = False  # one process, several meshes in turn
    mesh = fleet.init(is_collective=True,
                      strategy=fleet.DistributedStrategy(**strategy_kw),
                      devices=devices)
    paddle.seed(SEED)
    net = GPTForCausalLM(GPTConfig(dropout=0.0, **cfg_kw))
    opt = fleet.distributed_optimizer(
        popt.AdamW(learning_rate=1e-3, weight_decay=0.01))
    model = paddle.Model(net)
    model.prepare(optimizer=opt, loss=net.loss)
    proof = None
    if prove_spread:
        params, buffers = model._pull_state()
        model._ensure_opt_state(params, buffers)
        jax.block_until_ready((params, model._opt_state))
        after = [d.memory_stats()["bytes_in_use"] for d in devices]

        def homes(x):
            return {s.device for s in x.addressable_shards}

        w = net.gpt.blocks[0].attn.qkv.weight.value
        slots = [leaf for s in model._opt_state["slots"].values()
                 for leaf in s.values()]
        n_dev = len(devices)
        check(len(homes(w)) == n_dev,
              f"parameters sit on {len(homes(w))} of {n_dev} devices")
        check(all(len(homes(s)) == n_dev for s in slots),
              "an optimizer slot does not reach every device")
        check(all(a > b for a, b in zip(after, before)),
              f"bytes_in_use did not rise on every device: {before} -> "
              f"{after}")
        proof = {
            "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
            "mesh_device_coords": [
                [d.id, list(getattr(d, "coords", ()))]
                for d in mesh.devices.reshape(-1)],
            "param_shard_devices": len(homes(w)),
            "params_sharded": not w.sharding.is_fully_replicated,
            "slots_sharded": sum(
                not s.sharding.is_fully_replicated for s in slots),
            "slots": len(slots),
            "bytes_in_use_before": before, "bytes_in_use_after": after,
        }
    losses = []
    for _ in range(n_steps):
        loss, _ = model.train_batch([ids], [ids])
        losses.append(float(loss))
    check(np.isfinite(losses).all(), f"non-finite GPT loss: {losses}")
    del model, net, opt
    gc.collect()
    return losses, proof


def mesh_phase(mon, devices, cfg_kw=None, batch=8, seq=256, n_steps=3):
    from paddle_tpu.models.gpt import GPTConfig

    cfg_kw = dict(cfg_kw or {})
    vocab = GPTConfig(**cfg_kw).vocab_size
    ids = np.random.RandomState(SEED).randint(
        0, vocab, size=(batch, seq)).astype(np.int32)
    layouts = {
        "data2_model2": dict(dp_degree=2, tensor_parallel=True,
                             tensor_parallel_configs={
                                 "tensor_parallel_degree": 2}),
        "data2_sharding2": dict(dp_degree=2, sharding=True,
                                sharding_degree=2),
    }
    out = []
    results = {}
    for name, strategy_kw in layouts.items():
        snap = mon.snapshot()
        t0 = time.perf_counter()
        losses, proof = _mesh_losses(devices, strategy_kw, cfg_kw, ids,
                                     n_steps, prove_spread=True)
        results[name] = losses
        out.append({"phase": f"mesh4:{name}", "model": "gpt_small",
                    "batch": batch, "seq": seq, "steps": n_steps,
                    "losses": losses, **proof,
                    "smoke_wall_s": round(time.perf_counter() - t0, 2),
                    **mon.since(snap)})
    snap = mon.snapshot()
    t0 = time.perf_counter()
    ref, _ = _mesh_losses(devices[:1], {}, cfg_kw, ids, n_steps,
                          prove_spread=False)
    out.append({"phase": "mesh4:one_device_reference", "losses": ref,
                "smoke_wall_s": round(time.perf_counter() - t0, 2),
                **mon.since(snap)})
    check(ref[-1] < ref[0], f"GPT loss did not fall: {ref}")
    for name, losses in results.items():
        rel = max(abs(a - b) / abs(b) for a, b in zip(losses, ref))
        check(rel <= MESH_LOSS_RTOL,
              f"{name} losses {losses} vs one-device {ref}: rel {rel:.3g} "
              f"> {MESH_LOSS_RTOL}")
        out.append({"phase": f"mesh4:{name}:agreement", "max_rel": rel,
                    "rtol": MESH_LOSS_RTOL})
    return out


# -- entry --------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh path and its "
                         "one-device reference")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    dev = None
    try:
        dev = device_phase(args.chips)
        emit({"phase": "device", **dev})

        from paddle_tpu import sysconfig
        from paddle_tpu.framework.flags import set_flags

        cache_dir = sysconfig.enable_persistent_compilation_cache()
        # a device error must surface at its first occurrence, not after a
        # backoff loop: one attempt, and the phases assert zero retries
        set_flags({"transient_max_retries": 1})
        emit({"phase": "caches", "xla_cache_dir": cache_dir})
        mon = CompileMonitor()
        if args.chips == 4:
            import jax

            for line in mesh_phase(mon, jax.devices()[:4]):
                emit(line)
        else:
            for phase in (train_phase, resnet_phase, serve_phase):
                emit(phase(mon))
                gc.collect()  # the next phase needs the HBM this one held
        emit({"phase": "total",
              "smoke_wall_s": round(time.perf_counter() - t_start, 1)})
    except Exception as e:  # any failed phase fails the run — no carry-on
        import traceback

        traceback.print_exc()
        emit({"ok": False, "error": f"{type(e).__name__}: {e}"[:2000],
              "device": dev})
        return 1
    emit({"ok": True, "device": dev})
    return 0


if __name__ == "__main__":
    sys.exit(main())

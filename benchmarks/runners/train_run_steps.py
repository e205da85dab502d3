"""Training through ``fluid.Program`` -> ``Executor.run_steps``: windows of
``steps_per_window`` chained optimizer steps, one dispatch each, back to
back, each ended by a D2H read of the loss.

One object, the compiled chain with its state, is built in set-up, driven
from the seed through its first window by the call and feed the timed
windows use, and handed to the window.  After the window the plain
reference follows that first window step by step from the same seeded
weights, and three kinds of number are compared (see PERF.md for the
readings the limits were set from): every step's loss, the worst leaf's
first-moment norm (the gradients as the optimizer got them, beta1-averaged
over the window's steps), and the worst leaf's norm of the parameters'
change over the window.
"""
import gc
import time

import numpy as np

from benchmarks.harness import checks as hc


#: a leaf whose reference first moment is under this share of the median
#: leaf's has a gradient that is all but zero: under Adam its update is the
#: sign of rounding noise, so it is left out of the parameter-change
#: comparison (and stays in the first-moment one, floored by the median)
NO_GRADIENT = 1e-3


def _leaf_norms(main, names, weight_dict, parts_of):
    """Per-leaf norms of the optimizer's first moment and of the weights'
    change from the seeded weights (the f32 master where the optimizer
    keeps one), computed on the device."""
    import jax
    import jax.numpy as jnp

    def norm(x):
        return jnp.linalg.norm(x.astype(jnp.float32))

    @jax.jit
    def norms(slots, scope, w0):
        m = {n: slots[s]["moment1"] for s, n in names.items()}
        dp = {n: slots[s].get("master", scope[s]).astype(jnp.float32)
              - w0[n].astype(jnp.float32) for s, n in names.items()}
        return (hc.split_parts(norm, m, parts_of),
                hc.split_parts(norm, dp, parts_of))

    slots = main._opt_state["slots"]
    m, dp = jax.device_get(norms(slots, {s: main.scope[s] for s in names},
                                 weight_dict))
    # the moments themselves, on the host, for the direction comparison
    moments = {n: np.asarray(slots[s]["moment1"], np.float32)
               for s, n in names.items()}
    return ({k: float(v) for k, v in m.items()},
            {k: float(v) for k, v in dp.items()}, moments)


def run(ctx):
    import jax

    cfg, traffic, fam = ctx.config, ctx.traffic, ctx.family
    n = int(traffic["steps_per_window"])
    batch = int(traffic["batch"])
    feeds = ctx.generator.generate(traffic, cfg, ctx.seed)
    ctx.emit({"schedule": ctx.generator.summary(feeds), "steps_per_window": n})

    with ctx.phase("weights"):
        weights = fam.make_weights(cfg, ctx.seed)
        jax.block_until_ready(weights)
    with ctx.phase("model_build"):
        exe, main, loss, names = fam.build_program(cfg, traffic, weights,
                                                   feeds)
    const = tuple(feeds)

    def window():
        """The one call every window makes, timed or not."""
        t0 = time.perf_counter()
        with ctx.span("window_dispatch"):
            out, = exe.run_steps(main, feed=feeds, fetch_list=[loss],
                                 iterations=n, fetch_every=1,
                                 constant_feeds=const, return_numpy=False)
        with ctx.span("loss_readback"):
            losses = np.asarray(out, np.float32).reshape(-1)
        return losses, time.perf_counter() - t0

    with ctx.phase("compile_and_first_window"):
        first_losses, _ = window()
    with ctx.phase("state_norms"):
        m_prog, dp_prog, moments = _leaf_norms(main, names, weights,
                                                 fam.leaf_parts)
    with ctx.phase("warm_window"):
        window()
    ctx.setup_done()

    # -- the measured window ---------------------------------------------
    durations, last, window_losses = [], first_losses, []
    t0 = time.perf_counter()
    est = 0.0
    while time.perf_counter() - t0 + est < ctx.seconds:
        ctx.trace_tick(time.perf_counter() - t0)
        last, dt = window()
        durations.append(dt)
        window_losses.append(float(last[-1]))
        est = max(durations)
    total = time.perf_counter() - t0
    ctx.trace_stop()
    ctx.window_done()
    if durations and not ctx.rehearse:
        ctx.emit({"windows": len(durations),
                  "window_s_median": float(np.median(durations)),
                  "window_s_min": min(durations),
                  "window_s_max": max(durations),
                  "samples_per_s_median_window":
                  batch * n / float(np.median(durations)),
                  "loss_at_each_window_end": window_losses})
    ctx.metric("train_samples_s", len(durations) * n * batch / total)
    ctx.attempted = len(durations) * n
    ctx.facts.update(steps_per_window=n, samples_per_step=batch,
                     flops_per_step=fam.train_flops_per_sample(cfg, traffic)
                     * batch, step_module="jit_chain")

    # -- correct: outside the window ---------------------------------------
    ck = ctx.checks
    lim = ctx.cell["limits"]
    ck.true("loss_finite", bool(np.isfinite(first_losses).all()
                                and np.isfinite(last).all()))
    # the compared first window runs at a learning rate so small that its
    # bf16 weights do not move; by the last timed window (about 300 steps at
    # run_seconds) they have, and the loss on the fixed batch has fallen by a
    # third.  A step that does not feed its update into the next forward
    # pass leaves this ratio at 1
    ck.upper("loss_ratio", float(last[-1]) / float(first_losses[0]),
             lim["loss_ratio"], note=f"{float(first_losses[0])} -> "
             f"{float(last[-1])} over {(len(durations) + 2) * n} steps")
    ctx.read_memory(reserved_is_program_temp=True)
    exe.close()
    del exe, main, loss
    gc.collect()

    ref = ctx.reference
    opt = cfg["optimizer"]
    import jax.numpy as jnp

    p0 = {k: v.astype(jnp.float32) for k, v in weights.items()}
    jfeeds = {k: jnp.asarray(v) for k, v in feeds.items()}
    served = None if cfg["param_dtype"] == "float32" else cfg["param_dtype"]

    norm = lambda x: float(jnp.linalg.norm(x))  # noqa: E731

    def follow(mode):
        t = time.perf_counter()
        losses, p, st = ref.train_steps(
            p0, jfeeds, cfg, n, opt["learning_rate"],
            opt["weight_decay"], mode=mode, served_dtype=served,
            block_rows=ctx.cell.get("reference_block_rows", 32),
            warmup_steps=opt.get("warmup_steps", 0))
        m = hc.split_parts(norm, st["m"], fam.leaf_parts)
        dp = hc.split_parts(norm, {k: p[k] - p0[k] for k in p},
                            fam.leaf_parts)
        return losses, m, dp, st["m"], time.perf_counter() - t

    ref_losses, m_ref, dp_ref, mom_ref, ref_s = follow("f32")
    import statistics

    floor = NO_GRADIENT * statistics.median(m_ref.values())
    no_gradient = sorted(k for k, v in m_ref.items() if v < floor)
    ctx.emit({"leaves": len(m_ref), "leaves_without_gradient": no_gradient})
    ctx.emit({"reference_s": round(ref_s, 2), "reference_losses": ref_losses,
              "program_losses": [float(x) for x in first_losses]})

    def compare(tag, losses, m, dp, mom, record):
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
        g_gap, g_leaf = hc.worst_leaf_gap(m, m_ref)
        p_gap, p_leaf = hc.worst_leaf_gap(dp, dp_ref, skip=no_gradient)
        # norms are blind to zero-mean rounding noise (a leaf's norm moves by
        # half its square), so the moments' direction is compared too: the
        # norm of each leaf's difference from the reference's, against the
        # same denominators
        diff = hc.split_parts(norm, {k: jnp.asarray(mom[k]) - mom_ref[k]
                                     for k in mom_ref}, fam.leaf_parts)
        d_gap, d_leaf = hc.worst_leaf_share(diff, m_ref)
        record(f"{tag}loss_gap", loss_gap, lim["loss_gap"])
        record(f"{tag}grad_norm_gap", g_gap, lim["grad_norm_gap"],
               note=str(g_leaf))
        record(f"{tag}grad_direction_gap", d_gap, lim["grad_direction_gap"],
               note=str(d_leaf))
        record(f"{tag}param_change_gap", p_gap, lim["param_change_gap"],
               note=str(p_leaf))

    compare("", [float(x) for x in first_losses], m_prog, dp_prog, moments,
            ck.upper)
    if ctx.control:
        c_losses, m_c, dp_c, mom_c, _ = follow(ctx.control_mode)
        compare("control.", c_losses, m_c, dp_c, mom_c,
                ctx.control_checks.upper)

"""Mixture-of-experts FFN — GShard-style top-k routing over the
``expert`` mesh axis.

``MoELayer`` is a drop-in replacement for the dense ``ParallelMLP``:
same ``(x) -> y`` signature, same hidden→intermediate→hidden GELU FFN —
but the FFN weights are stacked ``[E, ...]`` per expert and each token is
processed by only the ``top_k`` experts its learned router picks.  The
design keeps every shape static so the layer composes with jit, scan
and the serving engine's closed compile set:

* **Router** — a replicated ``[D, E]`` gate; softmax over experts, then
  ``jax.lax.top_k``.  Training applies multiplicative jitter to the gate
  INPUT (GShard §3.1) drawn from :func:`current_rng_key`, so routing is
  deterministic under a fixed seed and exactly greedy in eval.
* **Capacity** — each expert accepts at most ``C = ceil(k*N*cf/E)``
  tokens (static, from shapes alone).  Slot positions come from a cumsum
  over the one-hot assignment flattened SLOT-MAJOR: every token's 1st
  choice beats any token's 2nd choice, and within a choice rank earlier
  tokens win — the deterministic tie-break the tests pin down.  Overflow
  tokens are dropped for that expert (their combine weight contributes
  nothing; with ``k > 1`` another expert usually still serves them).
* **Dispatch/combine** — one-hot einsums into/out of the ``[E, C, D]``
  capacity buffer, constrained to ``("expert", None, None)`` so GSPMD
  lowers them to all-to-alls over the ``expert`` mesh axis; the layer
  itself never calls a collective (same SPMD idiom as meta_parallel).
* **Expert FFN** — stacked weights named ``expert_*`` (the P506
  contract) with ``("expert", ...)`` partition specs.  On TPU with
  lane-aligned dims the matmuls go through the ``grouped_matmul`` Pallas
  kernel, which skips padding rows in-register; elsewhere the reference
  masked einsum (bit-identical by the kernel's parity test).
* **Aux loss** — the Switch Transformer load-balance loss
  ``E * Σ_e f_e · P_e`` (``f_e`` = fraction of selections, ``P_e`` =
  mean router probability); ≈ 1 when perfectly balanced.  It and the
  per-expert routed/dropped counters ride the trace-scoped
  :mod:`paddle_tpu.moe.stats` collector, keeping ``forward`` signature-
  compatible with the dense MLP.

Dense equivalence (the dryrun gate): with identically initialized
experts, ``top_k=1`` and capacity ≥ tokens, the combine weight is
``p/p == 1.0`` exactly and dispatch/combine are one-hot einsums
(``1.0*x + 0.0*pad``), so forward AND backward are bit-identical to the
dense MLP.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.meta_parallel import constrain
from ..nn import initializer as I
from ..nn.layer_base import Layer, current_rng_key
from . import stats as moe_stats

__all__ = ["MoELayer", "DroplessMoE", "gated_mlp"]


class MoELayer(Layer):
    """Top-k routed expert FFN; config knobs: ``moe_experts`` (E),
    ``moe_top_k``, ``moe_capacity_factor``, ``moe_jitter`` plus the dense
    MLP's ``hidden_size``/``intermediate_size``/``dropout``."""

    def __init__(self, cfg):
        super().__init__()
        D = cfg.hidden_size
        F = cfg.intermediate_size
        E = int(cfg.moe_experts)
        if E < 1:
            raise ValueError(f"MoELayer needs moe_experts >= 1, got {E}")
        self.num_experts = E
        self.top_k = max(1, min(int(getattr(cfg, "moe_top_k", 2)), E))
        self.capacity_factor = float(getattr(cfg, "moe_capacity_factor",
                                             1.25))
        self.jitter = float(getattr(cfg, "moe_jitter", 0.0))
        # replicated router gate; explicit fans so the stacked expert
        # weights initialize with the same scale a [D, F] dense layer gets
        self.gate = self.create_parameter(
            (D, E), default_initializer=I.XavierNormal())
        self.expert_fc1 = self.create_parameter(
            (E, D, F), default_initializer=I.XavierNormal(fan_in=D,
                                                          fan_out=F))
        self.expert_fc1.partition_spec = ("expert", None, None)
        self.expert_b1 = self.create_parameter((E, F), is_bias=True)
        self.expert_b1.partition_spec = ("expert", None)
        self.expert_fc2 = self.create_parameter(
            (E, F, D), default_initializer=I.XavierNormal(fan_in=F,
                                                          fan_out=D))
        self.expert_fc2.partition_spec = ("expert", None, None)
        self.expert_b2 = self.create_parameter((E, D), is_bias=True)
        self.expert_b2.partition_spec = ("expert", None)
        self.act = nn.GELU()
        self.drop = nn.Dropout(cfg.dropout)

    def capacity(self, num_tokens: int) -> int:
        """Static per-expert slot count for ``num_tokens`` routed rows."""
        return max(1, math.ceil(self.top_k * num_tokens *
                                self.capacity_factor / self.num_experts))

    def _expert_ffn(self, xe, group_sizes):
        """[E, C, D] -> [E, C, D]; rows past group_sizes[e] may hold
        garbage (FFN of a zero row is the bias path) — combine's one-hot
        weights never read them."""
        w1, w2 = self.expert_fc1.value, self.expert_fc2.value
        b1, b2 = self.expert_b1.value, self.expert_b2.value
        if self._use_kernel(xe):
            from ..ops.grouped_matmul import grouped_matmul

            h = grouped_matmul(xe, w1, group_sizes) + b1[:, None, :]
            h = self.act(h)
            return grouped_matmul(h, w2, group_sizes) + b2[:, None, :]
        h = jnp.einsum("ecd,edf->ecf", xe, w1) + b1[:, None, :]
        h = self.act(h)
        return jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]

    def _use_kernel(self, xe) -> bool:
        from ..ops.autotune import fused_epilogues_eligible

        D = xe.shape[-1]
        F = self.expert_fc1.value.shape[-1]
        return (fused_epilogues_eligible(D)
                and fused_epilogues_eligible(F))

    def forward(self, x):
        x = jnp.asarray(x)
        lead = x.shape[:-1]
        D = x.shape[-1]
        E, k = self.num_experts, self.top_k
        xf = x.reshape(-1, D)
        N = xf.shape[0]
        C = self.capacity(N)

        gate_in = xf
        if self.training and self.jitter > 0.0:
            eps = self.jitter
            gate_in = xf * jax.random.uniform(
                current_rng_key(), xf.shape, dtype=xf.dtype,
                minval=1.0 - eps, maxval=1.0 + eps)
        logits = gate_in @ self.gate.value
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)          # [N, k]
        # normalized combine weights.  For top-1 that is p/p: value 1.0
        # and derivative exactly zero, so spell it as the constant — the
        # autodiff of the quotient leaves last-ulp noise that would break
        # the dense-parity bit-identity; the router trains through the
        # balance loss (k == 1) or the relative weights (k > 1)
        if k == 1:
            combine_w = jnp.ones_like(top_p)
        else:
            combine_w = top_p / top_p.sum(-1, keepdims=True)

        onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)   # [N, k, E]
        # position-in-expert: cumsum in slot-major-then-token order, so
        # 1st choices beat 2nd choices and earlier tokens beat later ones
        flat = onehot.transpose(1, 0, 2).reshape(k * N, E)
        pos_flat = jnp.cumsum(flat, axis=0) - flat
        pos = pos_flat.reshape(k, N, E).transpose(1, 0, 2)   # [N, k, E]
        slot = (pos * onehot).sum(-1)                        # [N, k]
        kept = (slot < C) & (onehot.sum(-1) > 0)
        # one_hot of a negative index is all-zero: dropped slots vanish
        cap_oh = jax.nn.one_hot(jnp.where(kept, slot, -1), C,
                                dtype=jnp.float32)           # [N, k, C]
        oh_f = onehot.astype(jnp.float32)
        disp = jnp.einsum("nke,nkc->nec", oh_f, cap_oh)      # [N, E, C]
        comb = jnp.einsum("nke,nkc,nk->nec", oh_f, cap_oh,
                          combine_w.astype(jnp.float32))

        xe = jnp.einsum("nec,nd->ecd", disp.astype(xf.dtype), xf)
        xe = constrain(xe, "expert", None, None)
        selected = onehot.sum((0, 1))                        # [E] i32
        routed = jnp.minimum(selected, C).astype(jnp.int32)
        ye = self._expert_ffn(xe, routed)
        ye = constrain(ye, "expert", None, None)
        y = jnp.einsum("nec,ecd->nd", comb.astype(ye.dtype), ye)

        # Switch load-balance loss: E * sum_e f_e * P_e  (≈ 1 balanced)
        f = selected.astype(jnp.float32) / float(N * k)
        P = probs.mean(0)
        aux = float(E) * jnp.sum(f * P)
        moe_stats.record(aux, routed, (selected - routed).astype(jnp.int32),
                         pairs=N * k)

        return self.drop(y.reshape(*lead, D))


def gated_mlp(x, w_gate, w_up, w_down):
    """``(silu(x W_gate) * (x W_up)) W_down``: matmuls accumulate in
    float32, the product is rounded to ``x``'s dtype before ``W_down`` and
    the result is float32."""
    f32 = jnp.float32
    g = jnp.dot(x, jnp.asarray(w_gate), preferred_element_type=f32)
    u = jnp.dot(x, jnp.asarray(w_up), preferred_element_type=f32)
    return jnp.dot((g * jax.nn.sigmoid(g) * u).astype(x.dtype),
                   jnp.asarray(w_down), preferred_element_type=f32)


class DroplessMoE(Layer):
    """Routed experts with no capacity, plus shared experts.

    ``router="sigmoid"`` (the default): ``s = sigmoid(x W_g)`` in float32;
    the ``top_k`` experts are chosen by ``s + b`` (``score_bias``, a
    per-expert correction that steers load and never enters the weights),
    weighted by ``s`` of the chosen, normalised to sum 1 and scaled by
    ``routed_scale``.  ``router="softmax"``: ``p = softmax(x W_g)`` over ALL
    the router's experts in float32, the ``top_k`` by ``p``, weighted by
    ``p`` of the chosen (normalised over them with ``norm_topk``); no bias.
    Every expert is a gated-SiLU MLP ``(silu(x W_gate) * (x W_up))
    W_down``; the shared expert (``shared_experts`` of them fused into one
    of that many widths) sees every token once, scaled by ``sigmoid(x
    w_s)`` (``shared_gating``, ``[D, 1]``) where ``shared_gated``.

    ``held = (first, count)``: this layer holds experts ``first .. first +
    count - 1`` of the router's ``num_experts`` (one chip's share of a layer
    divided over several): the three expert tensors are ``[count, ...]``,
    the router keeps all its outputs and its ``top_k``, and the layer
    returns the part of the sum that its own experts give, plus the shared
    expert.  A (token, choice) pair whose expert is not held adds nothing,
    gets no row of the layout and costs no tile; nothing here stands in for
    the experts' other holders or for the exchange with them.

    Dispatch sorts the (token, choice) pairs by expert into
    ``ops.grouped_matmul.ragged_layout``'s tile-aligned rows and
    ``ragged_gated_mlp`` runs only the tiles in use, so no token is dropped
    whatever the routing and an expert nobody chose costs nothing.
    Per-expert routed counts OF THE HELD experts go to
    :mod:`paddle_tpu.moe.stats` (dropped is 0 by construction), with the
    pairs the router made in all."""

    #: pairs per expert under which a call is decode-like: the small row
    #: tile keeps the padding of one-token groups low
    _SMALL_ROWS = 16

    def __init__(self, hidden_size, expert_width, num_experts, top_k,
                 shared_experts=1, routed_scale=1.0, norm_topk=True,
                 dtype="float32", init_std=0.02, router="sigmoid",
                 held=None, shared_gated=False):
        super().__init__()
        D, F, E = int(hidden_size), int(expert_width), int(num_experts)
        if router not in ("sigmoid", "softmax"):
            raise ValueError(f"router must be 'sigmoid' or 'softmax', got "
                             f"{router!r}")
        self.num_experts, self.top_k = E, int(top_k)
        self.routed_scale, self.norm_topk = float(routed_scale), norm_topk
        self.router_kind = router
        self.held = None if held is None else (int(held[0]), int(held[1]))
        if self.held is not None and not (
                0 <= self.held[0] and 0 < self.held[1]
                and self.held[0] + self.held[1] <= E):
            raise ValueError(f"held={held!r} is no range of {E} experts")
        init = I.Normal(std=init_std)

        def p(shape, dt=dtype, spec=None):
            w = self.create_parameter(shape, dtype=dt,
                                      default_initializer=init)
            w.partition_spec = spec
            return w

        self.router = p((D, E))
        if router == "sigmoid":
            self.score_bias = p((E,), "float32")
        ex = ("expert", None, None)
        Eh = E if self.held is None else self.held[1]
        self.expert_gate = p((Eh, D, F), spec=ex)
        self.expert_up = p((Eh, D, F), spec=ex)
        self.expert_down = p((Eh, F, D), spec=ex)
        Fs = F * int(shared_experts)
        self.shared_gate = p((D, Fs)) if Fs else None
        self.shared_up = p((D, Fs)) if Fs else None
        self.shared_down = p((Fs, D)) if Fs else None
        self.shared_gating = p((D, 1)) if Fs and shared_gated else None

    @property
    def experts_held(self) -> int:
        return self.num_experts if self.held is None else self.held[1]

    def route(self, xf):
        """``[N, D]`` -> (expert ids ``[N, k]`` int32, weights ``[N, k]``
        float32), over all the router's experts."""
        f32 = jnp.float32
        logits = jnp.dot(xf.astype(f32), self.router.value.astype(f32),
                         precision=jax.lax.Precision.HIGHEST)
        if self.router_kind == "softmax":
            w, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                     self.top_k)
            if self.norm_topk:
                w = w / w.sum(-1, keepdims=True)
            return top_e.astype(jnp.int32), w * self.routed_scale
        s = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(s + self.score_bias.value.astype(f32),
                                 self.top_k)
        w = jnp.take_along_axis(s, top_e, axis=-1)
        if self.norm_topk:
            w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return top_e.astype(jnp.int32), w * self.routed_scale

    def forward(self, x):
        from ..ops.grouped_matmul import ragged_gated_mlp, ragged_layout

        x = jnp.asarray(x)
        lead, D = x.shape[:-1], x.shape[-1]
        xf = x.reshape(-1, D)
        N, k, E = xf.shape[0], self.top_k, self.num_experts
        with jax.named_scope("moe"):
            top_e, w = self.route(xf)
            A = N * k
            # the pairs an expert sees are A / E whatever share of the E is
            # held: A * held / E land here, on `held` experts
            tm = 16 if A < self._SMALL_ROWS * E else 128
            if self.held is None:
                lay = ragged_layout(top_e.reshape(-1), E, tm)
            else:
                lay = ragged_layout(top_e.reshape(-1) - self.held[0],
                                    self.held[1], tm, partial=True)
            rows = lay["tiles"] * tm
            # row r of the sorted layout reads token src[r]; N is a zero row
            # (a pair with no row has its dest past the layout: dropped)
            src = jnp.full((rows,), N, jnp.int32).at[lay["dest"]].set(
                jnp.arange(A, dtype=jnp.int32) // k,
                **({} if self.held is None else {"mode": "drop"}))
            xs = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)])[src]
            ys = ragged_gated_mlp(xs, self.expert_gate.value,
                                  self.expert_up.value,
                                  self.expert_down.value, lay)
            if self.held is None:
                yk = ys[lay["dest"]]
            else:
                # a tile nobody uses is never written: read no row of it
                yk = jnp.where(lay["present"][:, None],
                               ys[jnp.minimum(lay["dest"], rows - 1)], 0)
            y = jnp.einsum("nkd,nk->nd",
                           yk.reshape(N, k, D).astype(jnp.float32), w)
            if self.shared_gate is not None:
                shared = gated_mlp(xf, self.shared_gate.value,
                                   self.shared_up.value,
                                   self.shared_down.value)
                if self.shared_gating is not None:
                    shared = shared * jax.nn.sigmoid(jnp.dot(
                        xf, self.shared_gating.value,
                        preferred_element_type=jnp.float32))
                y = y + shared
            moe_stats.record(jnp.zeros((), jnp.float32), lay["counts"],
                             jnp.zeros((self.experts_held,), jnp.int32),
                             pairs=A)
        return y.astype(x.dtype).reshape(*lead, D)

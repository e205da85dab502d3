"""Trace-scoped MoE side-channel — aux losses and routing counters.

``MoELayer.forward`` must stay signature-compatible with the dense
``ParallelMLP`` (``(x) -> y``), so its auxiliary outputs — the
load-balance loss every MoE block contributes and the per-expert
routed/dropped counters the serving loop publishes — cannot ride the
return value.  They ride this collector instead: whoever owns the trace
(``GPTForCausalLM.forward`` for training, the serving engine's jitted
step bodies for decode) opens :class:`collect` around the model call and
reads the recorded TRACED values back inside the same trace.  Nothing
here crosses a jit boundary on its own; the collector is just a
trace-time mailbox.

The stack is thread-local: the serving decode loop traces in its own
thread while a training step traces in the main thread, and neither may
see the other's entries.
"""
from __future__ import annotations

import threading
from typing import List, Optional

import jax.numpy as jnp

__all__ = ["MoEStats", "collect", "record", "active"]

_local = threading.local()


def _stack() -> List["MoEStats"]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class MoEStats:
    """One trace's MoE entries: per-layer ``(aux, routed [E], dropped
    [E])`` triples, all traced arrays."""

    def __init__(self):
        self.entries: List[tuple] = []
        #: (token, choice) pairs the routers made, over the layers that said
        #: so: a static number, read at trace time
        self.pairs = 0

    def add(self, aux, routed, dropped, pairs=None):
        self.entries.append((aux, routed, dropped))
        self.pairs += int(pairs or 0)

    def total_aux(self):
        """Sum of the recorded load-balance losses (traced scalar), or
        ``None`` when no MoE layer ran."""
        if not self.entries:
            return None
        out = self.entries[0][0]
        for aux, _, _ in self.entries[1:]:
            out = out + aux
        return out

    def counts(self, num_experts: int):
        """``[2, E]`` int32 — row 0 routed tokens per expert, row 1
        dropped (capacity-overflow) tokens, summed over layers.  Zeros
        when no MoE layer ran."""
        routed = jnp.zeros((num_experts,), jnp.int32)
        dropped = jnp.zeros((num_experts,), jnp.int32)
        for _, r, d in self.entries:
            routed = routed + r
            dropped = dropped + d
        return jnp.stack([routed, dropped])

    def touched(self, num_experts: int):
        """``[E]`` int32 — in how many of the recorded layers each expert
        got at least one token; its sum over ``len(entries)`` is the mean
        count of experts a layer touched."""
        out = jnp.zeros((num_experts,), jnp.int32)
        for _, r, _ in self.entries:
            out = out + (r > 0).astype(jnp.int32)
        return out


class collect:
    """``with collect() as ms:`` — capture MoE records from the model
    calls inside the block (re-entrant; inner collectors shadow)."""

    def __enter__(self) -> MoEStats:
        st = MoEStats()
        _stack().append(st)
        return st

    def __exit__(self, *exc):
        _stack().pop()
        return False


def record(aux, routed, dropped, pairs=None):
    """Called by ``MoELayer.forward``; a no-op when nobody collects.
    ``pairs``: the (token, choice) pairs the layer's router made (static),
    of which ``routed`` counts those whose expert the layer holds."""
    st = _stack()
    if st:
        st[-1].add(aux, routed, dropped, pairs)


def active() -> bool:
    return bool(_stack())

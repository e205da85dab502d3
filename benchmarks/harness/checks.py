"""The numbers that decide ``correct``, each printed beside its limit."""


class Checks:
    def __init__(self, emit):
        self.rows = []
        self._emit = emit

    def upper(self, name, value, limit, note=""):
        """``value`` must not exceed ``limit``."""
        ok = bool(value == value and value <= limit)  # NaN fails
        self._row(name, value, limit, "<=", ok, note)

    def lower(self, name, value, limit, note=""):
        """``value`` must reach ``limit``."""
        ok = bool(value == value and value >= limit)
        self._row(name, value, limit, ">=", ok, note)

    def true(self, name, ok, note=""):
        self._row(name, bool(ok), True, "is", bool(ok), note)

    def _row(self, name, value, limit, op, ok, note):
        if hasattr(value, "item"):  # a numpy scalar: a plain number
            value = value.item()
        row = {"check": name, "value": value, "op": op, "limit": limit,
               "ok": ok}
        if note:
            row["note"] = note
        self.rows.append(row)
        self._emit(row)

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def worst_leaf_share(numerators, reference_norms, skip=()):
    """Worst leaf of ``numerator / max(reference norm of that leaf, of the
    median leaf)``: some gradients are all but zero, and a leaf must not be
    judged against nothing.  Leaves named in ``skip`` are left out.
    Returns (share, "leaf=share" of the five worst); NaN is the worst."""
    import statistics

    floor = statistics.median(reference_norms.values())
    shares = {}
    for k, ref in reference_norms.items():
        if k in skip:
            continue
        x = numerators[k] / max(ref, floor, 1e-30)
        shares[k] = float("inf") if x != x else x
    top = sorted(shares.items(), key=lambda kv: -kv[1])[:5]
    worst = top[0][1] if top else float("nan")
    if worst == float("inf"):
        worst = float("nan")
    return worst, ", ".join(f"{k}={v:.4g}" for k, v in top)


def worst_leaf_gap(program_norms, reference_norms, skip=()):
    """Worst leaf of | ||program|| - ||reference|| | (the gap between the two
    norms, not the norm of a difference), by :func:`worst_leaf_share`."""
    return worst_leaf_share(
        {k: abs(program_norms[k] - ref) for k, ref in reference_norms.items()},
        reference_norms, skip)


def split_parts(norm_fn, tree, parts_of):
    """Per-leaf norms with fused leaves taken apart: ``parts_of(name)`` says
    into how many equal parts the last axis of a leaf splits (a fused QKV
    projection is three leaves for this comparison: the key bias has no
    gradient at all, and must not be judged with the query's and value's)."""
    out = {}
    for name, x in tree.items():
        n = parts_of(name)
        if n == 1:
            out[name] = norm_fn(x)
        else:
            w = x.shape[-1] // n
            for i in range(n):
                out[f"{name}[{i}/{n}]"] = norm_fn(x[..., i * w:(i + 1) * w])
    return out

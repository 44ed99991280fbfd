"""``{"call": "tpch_q1", "shipdate_to": d, "groups": 6, "limits": {"sums":
..}}``: TPC-H Q1, "Pricing Summary Report", over a resident LINEITEM share
(``operands/lineitem.py``):

    select l_returnflag, l_linestatus, sum(l_quantity),
           sum(l_extendedprice), sum(l_extendedprice * (1 - l_discount)),
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)),
           avg(l_quantity), avg(l_extendedprice), avg(l_discount), count(*)
    from lineitem where l_shipdate <= :d
    group by l_returnflag, l_linestatus

in Bolt's idiom a filter folded by a group id that is a function of the
record, ONE call on the public API:

    sums, counts = bolt.ops.segment_reduce(
        b.filter(pred), labels=group, num_segments=6, value=terms,
        return_counts=True)

with ``group = 3 * l_linestatus + l_returnflag`` and ``terms`` the six sums
a group ``(qty, price, price * (100 - disc), price * (100 - disc) * (100 +
tax), disc, 1)``, discounts and taxes in percent; the three averages are
quotients the caller takes.  The handle is the pair; fetch ``fold_parts``
brings it to the host as ``{"sums": (groups, 6), "counts": (groups,)}``.
A terminal; needs an operand whose reference gives exact ``totals``.

What is compared: every COUNT exactly (a count that differs is infinitely
far), and ``sums``, the worst ``|got - want| / max(want, 1)`` over the
``groups x 6`` sums.  The reference is exact integer arithmetic (a row's
charge reaches 1.1e11: it is carried as two products below 2**23); the
control holds the table, every term and every answer in bfloat16
(``reference.bf16``), which also moves the selection and the counts."""

import functools

import numpy as np

import spectral

DATE, QTY, PRICE, DISC, TAX, FLAG, STATUS = range(7)
TERMS = 6


def _args(step):
    return int(step["shipdate_to"]), int(step["groups"])


def bind(step, man):
    import jax.numpy as jnp
    import bolt_tpu as bolt
    day, groups = _args(step)

    def pred(r):
        return r[DATE] <= day

    def group(r):
        return (3 * r[STATUS] + r[FLAG]).astype(jnp.int32)

    def terms(r):
        disc_price = r[PRICE] * (100 - r[DISC])
        return (r[QTY], r[PRICE], disc_price, disc_price * (100 + r[TAX]),
                r[DISC], jnp.ones_like(r[QTY]))
    return lambda a: bolt.ops.segment_reduce(
        a.filter(pred), labels=group, num_segments=groups, value=terms,
        return_counts=True)


def plan(p, step):
    if p.windowed or p.bodies:
        raise ValueError("tpch_q1 reads the whole table as it is")
    p.terminal = Q1(_args(step), step.get("limits", {}))


def traffic(step, t):
    """All seven columns, read once; the answer is a few dozen numbers."""
    t.read, t.written = 7 * t.sizes[0], 0
    t.sizes = []


@functools.lru_cache(maxsize=None)
def _terms(args, lowp):
    day, groups = args
    if lowp:
        import reference
        bf16 = reference.bf16

        def terms(cols):
            cols = [bf16(c) for c in cols]
            gid = 3 * cols[STATUS] + cols[FLAG]
            keep = cols[DATE] <= day
            dp = bf16(cols[PRICE] * (100 - cols[DISC]))
            return ([keep & (gid == g) for g in range(groups)],
                    [cols[QTY], cols[PRICE], dp,
                     bf16(dp * (100 + cols[TAX])), cols[DISC],
                     cols[QTY] * 0 + 1])
        return terms

    def terms(cols):
        gid = 3 * cols[STATUS] + cols[FLAG]
        keep = cols[DATE] <= day
        dp = cols[PRICE] * (100 - cols[DISC])        # below 2**31
        more = 100 + cols[TAX]
        return ([keep & (gid == g) for g in range(groups)],
                [cols[QTY], cols[PRICE], dp, (dp & 0x7FFF) * more,
                 (dp >> 15) * more, cols[DISC], cols[QTY] * 0 + 1])
    return terms


class Q1:
    def __init__(self, args, limits):
        self.args, self.limits = args, limits

    def parts(self, got, want):
        try:
            sums = np.asarray(got["sums"], np.float64)
            counts = np.asarray(got["counts"])
            ok = (sums.shape == want["sums"].shape
                  and counts.shape == want["counts"].shape
                  and np.issubdtype(counts.dtype, np.integer)
                  and np.all(np.isfinite(sums)))
        except (KeyError, TypeError, ValueError):
            ok = False
        if not ok:
            return {"counts": float("inf"), "sums": float("inf")}
        wrong = int((counts.astype(np.int64) != want["counts"]).sum())
        return {"counts": float(wrong),
                "sums": float(np.max(np.abs(sums - want["sums"])
                                     / np.maximum(np.abs(want["sums"]),
                                                  1.0)))}

    def number(self, p, got, want):
        parts = self.parts(got, want)
        if parts.pop("counts"):
            return float("inf")          # every COUNT is exact
        return spectral.worst(parts, self.limits)

    def resident_expected(self, ref, p):
        rows = ref.totals(_terms(self.args, False))
        sums = np.asarray(
            [[q, pr, dp, lo + (hi << 15), disc, one]
             for q, pr, dp, lo, hi, disc, one in rows], dtype=object)
        return {"sums": sums.astype(np.float64),
                "counts": np.asarray([r[-1] for r in rows], np.int64)}

    def resident_lowp(self, ref, p):
        rows = np.asarray(ref.totals(_terms(self.args, True), lowp=True))
        sums = spectral.bf16(rows.astype(np.float32))
        return {"sums": sums, "counts": sums[:, -1].astype(np.int64)}

    resident_bf16 = resident_lowp

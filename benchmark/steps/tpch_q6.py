"""``{"call": "tpch_q6", "shipdate": [from, before], "discount": [lo, hi],
"quantity_below": q, "limits": {"revenue": ..}}``: TPC-H Q6, "Forecasting
Revenue Change", over a resident LINEITEM share (``operands/lineitem.py``):

    select sum(l_extendedprice * l_discount) from lineitem
    where l_shipdate >= :from and l_shipdate < :before
      and l_discount between :lo and :hi and l_quantity < :q

in Bolt's idiom a filter, a record-wise map and a sum:

    b.filter(pred).map(lambda r: r[PRICE] * r[DISC]).sum()

Days count from 1992-01-01, prices are cents and discounts percent, so the
answer is in cents x percent.  A terminal; needs an operand whose
reference gives exact ``totals``.

What is compared: ``revenue``, ``|got - want| / want``.  The reference is
exact integer arithmetic; the control holds the table, the product and the
answer in bfloat16 (``reference.bf16``), which also moves the selection
(a day number past 256 is not a bfloat16)."""

import functools

import numpy as np

import spectral

DATE, QTY, PRICE, DISC = 0, 1, 2, 3


def _args(step):
    (d0, d1), (c0, c1) = step["shipdate"], step["discount"]
    return int(d0), int(d1), int(c0), int(c1), int(step["quantity_below"])


def bind(step, man):
    d0, d1, c0, c1, q = _args(step)

    def pred(r):
        return ((r[DATE] >= d0) & (r[DATE] < d1) & (r[DISC] >= c0)
                & (r[DISC] <= c1) & (r[QTY] < q))

    def revenue(r):
        return r[PRICE] * r[DISC]
    return lambda a: a.filter(pred).map(revenue).sum()


def plan(p, step):
    if p.windowed or p.bodies:
        raise ValueError("tpch_q6 reads the whole table as it is")
    p.terminal = Q6(_args(step), step.get("limits", {}))


def traffic(step, t):
    """The four columns the query names, read once; the answer is a
    scalar.  Counted on the table as the query sees it, four bytes a value,
    whatever the device pads a row to."""
    t.read, t.written = 4 * t.sizes[0], 0
    t.sizes = []


@functools.lru_cache(maxsize=None)
def _terms(args, lowp):
    d0, d1, c0, c1, q = args
    if lowp:
        import reference
        bf16 = reference.bf16
    else:
        def bf16(x):
            return x

    def terms(cols):
        cols = [bf16(c) for c in cols]
        keep = ((cols[DATE] >= d0) & (cols[DATE] < d1) & (cols[DISC] >= c0)
                & (cols[DISC] <= c1) & (cols[QTY] < q))
        return [keep], [bf16(cols[PRICE] * cols[DISC])]
    return terms


class Q6:
    def __init__(self, args, limits):
        self.args, self.limits = args, limits

    def parts(self, got, want):
        got = np.asarray(got, np.float64)
        if got.shape != () or not np.isfinite(got):
            return {"revenue": float("inf")}
        return {"revenue": abs(float(got) - want) / max(abs(want), 1.0)}

    def number(self, p, got, want):
        return spectral.worst(self.parts(got, want), self.limits)

    def resident_expected(self, ref, p):
        return float(ref.totals(_terms(self.args, False))[0][0])

    def resident_lowp(self, ref, p):
        total = ref.totals(_terms(self.args, True), lowp=True)[0][0]
        return float(spectral.bf16(np.float32(total).reshape(1))[0])

    resident_bf16 = resident_lowp

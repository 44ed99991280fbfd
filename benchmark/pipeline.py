"""From a traffic file's request kinds to calls on the system under test.

A request is data: an operand, a list of ``steps`` through the PUBLIC bolt
API, and how the caller takes the answer (``fetch``).  Nothing here knows a
call, an operand or a fetch by name; each is a file found through the
manifest, so a new one is a new file and no edit:

    steps/<call>.py      ``bind(step, man) -> (handle -> handle)``, the
                         program's side of ``{"call": <call>, ...}``; beside
                         it the reference's side ``plan(p, step)`` and the
                         roofline's ``traffic(step, t)``
    fns/<fn>.py          a map body: ``body`` and, spelled again for the
                         reference, ``reference`` and ``REACH``
    fetches/<fetch>.py   ``take(handle) -> answer`` and ``ON_DEVICE``
    operands/<name>.py   ``make(spec, config, mesh, seed)``: what the steps
                         are applied to, and the reference over the same data

A string ``"$name"`` inside a step is replaced by the request's own
position (one entry of the kind's ``positions`` table).  The operands and
``drivers/`` are the only places of the benchmark that touch ``bolt_tpu``.
"""

import numpy as np


def substitute(node, position):
    """``node`` with every ``"$name"`` replaced from ``position``."""
    if isinstance(node, str) and node.startswith("$"):
        return position[node[1:]]
    if isinstance(node, list):
        return [substitute(n, position) for n in node]
    if isinstance(node, dict):
        return {k: substitute(v, position) for k, v in node.items()}
    return node


def expand(traffic):
    """The fixed multiset of requests of one cycle, before any permutation:
    a list of ``(kind_index, position_index, steps)``.  A kind's ``count``
    requests take its positions in table order, wrapping, so the multiset is
    the same for every seed."""
    out = []
    for k, kind in enumerate(traffic["requests"]):
        positions = kind.get("positions") or [{}]
        for i in range(int(kind["count"])):
            p = i % len(positions)
            out.append((k, p, substitute(kind["steps"], positions[p])))
    return out


def cycle_order(n, seed):
    """The seed's order of the ``n`` requests of a cycle."""
    return np.random.default_rng(int(seed)).permutation(n)


def compile_call(man, steps):
    """``steps`` as one callable ``operand -> handle``, built once so the
    timed loop interprets nothing."""
    ops = [man.module("steps", s["call"]).bind(s, man) for s in steps]

    def run(operand):
        for op in ops:
            operand = op(operand)
        return operand
    return run


def mesh_of(chips):
    """The first ``chips`` devices as the 1-d mesh ``default_mesh`` makes."""
    import jax
    return jax.sharding.Mesh(np.asarray(jax.devices()[:chips]), ("k",))

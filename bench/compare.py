"""The comparison that decides ``correct``: the program's training
against the plain reference's, on what a user of the engine observes.

Three numbers, each with a limit of its own (``bench/limits/<config>.json``):

``decisions_differ``
    discrete outcomes that differ: each certificate change (which
    worker, in which order, at which simulated clock), each final
    model's stumps (feature, threshold, sign, count), the adoptions,
    the round the target was reached, and evictions from the pending
    queues (there must be none). Exact: limit 0.
``cert_gap``
    the widest gap between a certificate of the program and the
    reference's, over the history and the final certificates, as a
    share of the largest certificate's size.
``alpha_gap``
    the widest relative gap between a stump weight of the program's
    final models and the reference's.

Where the reference's run stopped being exact (a resample drawn after a
fire, see ``reference.py``), only the history before that round is
compared; the final models and certificates are not.
"""

from __future__ import annotations

import numpy as np

#: simulated clocks are sums of whole costs; they agree to rounding
CLOCK_RTOL = 1e-6


def compare(prog: dict, ref: dict, *, history: bool = True) -> dict[str, float]:
    """The numbers compared; ``history`` is False where the training ran
    without recording its certificate history."""
    differ = 0
    exact_until = ref["exact_until"]
    if not history:
        n_hist = 0
        differ += int(prog["rounds"] != ref["rounds"])
        differ += int(exact_until is None and prog["accepted"] != ref["accepted"])
    elif exact_until is None:
        n_hist = len(ref["history"])
        differ += int(len(prog["history"]) != n_hist)
        differ += int(prog["rounds"] != ref["rounds"])
        differ += int(prog["accepted"] != ref["accepted"])
    else:
        n_hist = sum(1 for r in ref["history_rounds"] if r < exact_until)
        differ += int(len(prog["history"]) < n_hist)
    differ += int(prog["evicted"] != 0)

    cert_gaps = [0.0]
    scale = max([1e-3] + [abs(c) for _, _, c in ref["history"]])
    for (pc, pw, pv), (rc, rw, rv) in zip(prog["history"][:n_hist], ref["history"][:n_hist]):
        if pw != rw or abs(pc - rc) > CLOCK_RTOL * max(abs(rc), 1.0):
            differ += 1
        cert_gaps.append(abs(pv - rv) / scale)

    alpha_gap = 0.0
    if exact_until is None:
        pm, rm = prog["models"], ref["models"]
        pf, rf = np.asarray(prog["final_certificates"]), np.asarray(ref["final_certificates"])
        cert_gaps.extend((np.abs(pf - rf) / scale).tolist())
        for i in range(len(rm["count"])):
            k = int(rm["count"][i])
            if int(pm["count"][i]) != k:
                differ += 1
                k = min(k, int(pm["count"][i]))
            same = all(np.array_equal(pm[f][i, :k], rm[f][i, :k]) for f in ("feat", "thr", "sign"))
            differ += int(not same)
            if k:
                a_p, a_r = pm["alpha"][i, :k].astype(np.float64), rm["alpha"][i, :k].astype(np.float64)
                alpha_gap = max(alpha_gap, float(np.max(np.abs(a_p - a_r) / np.abs(a_r))))
    return {
        "decisions_differ": float(differ),
        "cert_gap": float(max(cert_gaps)),
        "alpha_gap": alpha_gap,
    }

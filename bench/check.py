"""The comparison that decides ``correct``.

Every request submitted in the measured window is compared, once its
answer has come, with the closed form of its integrals in float64
(``reference/<form>.py``).  The numbers compared:

* ``missing``: answers that never came, came as a failure, came with
  another number of integrands than asked for, or hold a value that is not
  finite.  Exact: limit 0.
* ``n_mismatch`` (fixed sample budget): answers whose sample count is not
  the budget rounded up to whole rounds.  Exact: limit 0.
* ``stderr_over_target`` (stderr target): the largest served stderr over
  the target.  The configuration states the limit: 1.
* ``chi2_excess``: |mean of z^2 - 1| over every integrand of every answer,
  with z = (estimate - exact) / sigma.  For a fixed sample budget sigma is
  the exact standard deviation of the plain estimate at the served sample
  count, sqrt((E f^2 - (E f)^2) / n), so a wrong sample count or a missing
  part of the sum shows as well as a biased estimate.  With a stderr
  target the estimator is importance-sampled and its variance has no
  closed form: sigma is the served stderr.  The limit is measured
  (``check`` in the configuration).

Where the configuration keeps a durable store, ``durability.py`` adds two
exact numbers, ``unjournaled`` and ``unsynced``.
"""

from __future__ import annotations

import math

import numpy as np


def expected_samples(request: dict, round_samples: int) -> int | None:
    n = request.get("n_samples")
    if n is None:
        return None
    return math.ceil(int(n) / round_samples) * round_samples


def compare(answers, request: dict, reference, round_samples: int,
            limits: dict) -> dict:
    """``answers``: ``(params, result)`` per request due in the window, with
    ``result`` None when no answer came; a result has ``means``,
    ``stderrs``, ``n_per_family`` and ``failed``.  Returns ``{name:
    {"value", "limit"}}`` in a fixed order."""
    n_fn = int(request["n_fn"])
    want_n = expected_samples(request, round_samples)
    target = request.get("target_stderr")
    missing = n_mismatch = 0
    z2 = []
    worst_se = 0.0
    for params, res in answers:
        if res is None or getattr(res, "failed", False):
            missing += 1
            continue
        means = np.asarray(res.means, np.float64)
        ses = np.asarray(res.stderrs, np.float64)
        ns = np.asarray(res.n_per_family, np.float64)
        # a request is one family of n_fn integrands
        if (means.shape != (n_fn,) or ses.shape != (n_fn,)
                or ns.shape != (1,) or not np.all(np.isfinite(means))
                or not np.all(np.isfinite(ses)) or ns[0] <= 0):
            missing += 1
            continue
        n = float(ns[0])
        if want_n is not None and n != want_n:
            n_mismatch += 1
        exact = reference.exact(params)
        if target is None:
            var = reference.second_moment(params) - exact * exact
            sigma = np.sqrt(var / n)
        else:
            sigma = ses
            worst_se = max(worst_se, float(ses.max()))
        with np.errstate(divide="ignore", invalid="ignore"):
            z = (means - exact) / sigma
        # a zero stderr beside a wrong estimate is infinitely far off
        z2.append(np.where(np.isnan(z), np.inf, z) ** 2)
    out = {"missing": {"value": missing, "limit": 0}}
    if want_n is not None:
        out["n_mismatch"] = {"value": n_mismatch, "limit": 0}
    if target is not None:
        out["stderr_over_target"] = {"value": worst_se / float(target),
                                     "limit": 1.0}
    chi2 = (abs(float(np.concatenate(z2).mean()) - 1.0) if z2
            else float("inf"))
    out["chi2_excess"] = {"value": chi2,
                          "limit": float(limits["chi2_excess"])}
    return out


def passed(numbers: dict) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())


def lines(numbers: dict) -> list[str]:
    return [f"check {name} {v['value']!r} limit {v['limit']!r}"
            for name, v in numbers.items()]

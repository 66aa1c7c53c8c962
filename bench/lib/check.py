"""How `correct` is decided: every answer against the plain reference.

An answer is (pool index, path, score) as the client received it.  The
reference decodes each pool entry once (`reference.viterbi_reference`, f32
on the device) and every path is scored in float64 on the host.  The numbers
compared, each with a limit from the configuration file:

* ``path_gap``: the largest relative shortfall of an answer's path below the
  reference path, (S(ref) - S(answer)) / |S(ref)| with S the float64 path
  log-likelihood (the paper's eta, Sec. VII-D).  An exact decoder in float32
  may break a near-tie the other way; it cannot fall short by more than
  rounding.
* ``score_err``: the largest relative gap between the score the program
  reported and the float64 score of the path it reported.
* ``malformed``: answers of the wrong length or with a state out of range;
  limit 0.
* ``prefix_bad`` (streams only): sessions whose committed prefixes, joined,
  are not the path `finish` returned; limit 0.

``paths_differ`` (answers whose path is not the reference's) is reported and
not compared: a near-tie broken the other way is still an optimal path.
"""

from __future__ import annotations

import math

import numpy as np

from .reference import path_score64, viterbi_reference


def reference_for(log_pi, log_A, pool: np.ndarray, lengths: np.ndarray,
                  used: np.ndarray, mode: str = "f32") -> dict:
    """Reference (path, score) for each pool index in `used`."""
    used = np.unique(np.asarray(used, np.int64))
    paths, scores = viterbi_reference(log_pi, log_A, pool[used],
                                      lengths[used], mode=mode)
    return {int(i): (paths[j, :lengths[i]], float(scores[j]))
            for j, i in enumerate(used)}


def judge(answers, ref: dict, log_pi, log_A, pool: np.ndarray,
          lengths: np.ndarray) -> dict:
    """Readings over every answer (see the module docstring)."""
    lp64 = np.asarray(log_pi, np.float64)
    la64 = np.asarray(log_A, np.float64)
    K = la64.shape[0]
    ref64 = {i: path_score64(lp64, la64, pool[i], p)
             for i, (p, _) in ref.items()}
    gap = err = 0.0
    differ = malformed = 0
    for i, path, score in answers:
        path = np.asarray(path)
        L = int(lengths[i])
        if (path.shape != (L,) or path.min() < 0 or path.max() >= K
                or not math.isfinite(score)):
            malformed += 1
            continue
        rpath = ref[i][0]
        if np.array_equal(path, rpath):
            s64 = ref64[i]
        else:
            differ += 1
            s64 = path_score64(lp64, la64, pool[i], path)
            gap = max(gap, (ref64[i] - s64) / abs(ref64[i]))
        err = max(err, abs(score - s64) / abs(s64))
    return {"checked": len(answers), "path_gap": gap, "score_err": err,
            "malformed": malformed, "paths_differ": differ}


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {value, limit}}) for the numbers that are compared."""
    checks = {name: {"value": readings[name], "limit": limits[name]}
              for name in limits if name in readings}
    ok = readings.get("checked", 0) > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return ok, checks

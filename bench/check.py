"""The comparison that decides ``correct``.

Every request sent in the window must come back with an answer
(``missing``, limit 0).  The answers given for a sample of the frame pool,
drawn from the seed, are then held to the plain reference
(:mod:`reference`) run once over those frames:

* float configurations: ``wrong_share`` is the share of the sampled frames
  whose float64 run decides every threshold by at least ``tie_eps`` and
  that received any answer other than the reference's class.  A frame
  whose reference holds a decision within ``tie_eps`` of its threshold is
  a tie: float32 arithmetic in another summation order may take either
  side of it, so it is counted (``ties``) but not judged.
* integer configurations: the integer twin is exact, so every sampled
  frame is judged.

The limits live in the configuration file (``check.limits``), with the
readings they were set from in ``PERF.md``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

import reference


def sample_frames(seed: int, pool_size: int, n: int) -> np.ndarray:
    """Sorted pool indices to judge, drawn from the seed."""
    if n >= pool_size:
        return np.arange(pool_size)
    rng = np.random.default_rng([int(seed), 0xC4EC])
    return np.sort(rng.choice(pool_size, n, replace=False))


def reference_classes(cfg: dict, iq: np.ndarray, weights: dict
                      ) -> Tuple[np.ndarray, np.ndarray]:
    """(class per frame, True where the frame is judged) from the reference."""
    check = cfg["check"]
    if check["reference"] == "integer":
        logits = reference.integer_reference(iq, weights, cfg["network"],
                                             int(check["bits"]))
        return logits.argmax(axis=1), np.ones(iq.shape[0], bool)
    logits, margin = reference.float_reference(iq, weights, cfg["network"])
    return logits.argmax(axis=1), margin >= float(check["tie_eps"])


def wrong_share(frames: np.ndarray, answers: np.ndarray, sample: np.ndarray,
                ref_class: np.ndarray, judged: np.ndarray) -> Tuple[float, int]:
    """Share of judged sampled frames that got any wrong answer.

    ``frames``/``answers`` are the answered requests (pool index, class);
    ``sample`` the sampled pool indices with their ``ref_class`` and
    ``judged`` flags.  Returns (share, number of frames judged).
    """
    pos = np.searchsorted(sample, frames)
    pos = np.minimum(pos, sample.size - 1)
    hit = sample[pos] == frames
    pos, answers = pos[hit], answers[hit]
    answered = np.zeros(sample.size, bool)
    answered[pos] = True
    wrong = np.zeros(sample.size, bool)
    wrong[pos[answers != ref_class[pos]]] = True
    considered = answered & judged
    n = int(considered.sum())
    return (float((wrong & considered).sum()) / n if n else 1.0), n


def compare(cfg: dict, seed: int, pool_iq: np.ndarray, weights: dict,
            frames: np.ndarray, answers: np.ndarray, missing: int
            ) -> Tuple[Dict[str, dict], Dict[str, float]]:
    """The numbers compared, each with its limit, and what else was seen."""
    check = cfg["check"]
    sample = sample_frames(seed, pool_iq.shape[0], int(check["sample_frames"]))
    ref_class, judged = reference_classes(cfg, pool_iq[sample], weights)
    share, n = wrong_share(frames, answers, sample, ref_class, judged)
    limits = check["limits"]
    numbers = {
        "missing": {"value": int(missing), "limit": int(limits["missing"])},
        "wrong_share": {"value": share, "limit": float(limits["wrong_share"])},
    }
    info = {"frames_judged": n, "ties": int((~judged).sum()),
            "frames_sampled": int(sample.size)}
    return numbers, info


def passed(numbers: Dict[str, dict]) -> bool:
    return all(v["value"] <= v["limit"] for v in numbers.values())

"""Evaluation metrics.

Reference: python/hetu/metrics.py (AUC:120 via thresholded confusion
matrices, f_score:315, precision/recall/accuracy).  Host-side numpy
implementations with the same capability surface.
"""

from __future__ import annotations

import numpy as np

__all__ = [
    "accuracy", "confusion_matrix", "precision", "recall", "f_score", "auc_roc",
    "auc_pr",
]


def _np(x):
    return np.asarray(x)


def accuracy(pred_labels, true_labels) -> float:
    pred_labels, true_labels = _np(pred_labels), _np(true_labels)
    return float((pred_labels == true_labels).mean())


def confusion_matrix(pred, truth, threshold: float = 0.5):
    """Binary confusion counts (tp, fp, fn, tn) at a threshold
    (reference metrics.py thresholded counters)."""
    pred, truth = _np(pred).ravel(), _np(truth).ravel()
    p = pred >= threshold
    t = truth.astype(bool)
    tp = int(np.sum(p & t))
    fp = int(np.sum(p & ~t))
    fn = int(np.sum(~p & t))
    tn = int(np.sum(~p & ~t))
    return tp, fp, fn, tn


def precision(pred, truth, threshold: float = 0.5) -> float:
    tp, fp, fn, tn = confusion_matrix(pred, truth, threshold)
    return tp / max(tp + fp, 1)


def recall(pred, truth, threshold: float = 0.5) -> float:
    tp, fp, fn, tn = confusion_matrix(pred, truth, threshold)
    return tp / max(tp + fn, 1)


def f_score(pred, truth, threshold: float = 0.5, beta: float = 1.0) -> float:
    """F-beta (reference metrics.py:315)."""
    p = precision(pred, truth, threshold)
    r = recall(pred, truth, threshold)
    if p + r == 0:
        return 0.0
    b2 = beta * beta
    return (1 + b2) * p * r / (b2 * p + r)


def auc_roc(scores, truth) -> float:
    """ROC-AUC by rank statistic (equivalent to the reference's threshold
    sweep metrics.py:120, exact rather than binned)."""
    scores, truth = _np(scores).ravel(), _np(truth).ravel().astype(bool)
    n_pos = int(truth.sum())
    n_neg = truth.size - n_pos
    if n_pos == 0 or n_neg == 0:
        return 0.5
    order = np.argsort(scores, kind="mergesort")
    ranks = np.empty_like(order, dtype=np.float64)
    # average ranks for ties
    sorted_scores = scores[order]
    i = 0
    r = 1
    while i < len(sorted_scores):
        j = i
        while j + 1 < len(sorted_scores) and sorted_scores[j + 1] == sorted_scores[i]:
            j += 1
        ranks[order[i:j + 1]] = 0.5 * (r + r + (j - i))
        r += j - i + 1
        i = j + 1
    return float((ranks[truth].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def auc_pr(scores, truth, num_thresholds: int = 200) -> float:
    """PR-AUC via threshold sweep (reference metrics.py ROC-PR)."""
    scores, truth = _np(scores).ravel(), _np(truth).ravel().astype(bool)
    thresholds = np.linspace(scores.min(), scores.max(), num_thresholds)
    ps, rs = [], []
    for th in thresholds[::-1]:
        p = scores >= th
        tp = np.sum(p & truth)
        fp = np.sum(p & ~truth)
        fn = np.sum(~p & truth)
        ps.append(tp / max(tp + fp, 1))
        rs.append(tp / max(tp + fn, 1))
    return float(np.trapezoid(ps, rs))

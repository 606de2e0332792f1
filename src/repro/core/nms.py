"""Non-max suppression + capacity-K keypoint selection (static shapes).

MapReduce emits variable-length keypoint lists; SPMD needs fixed shapes.
A detector's dense response map goes through 3x3 NMS, halo/interior
ownership masking, then top-K selection per tile.  Counts are computed on
the *dense* thresholded map (before truncation) so Table-2 numbers are
exact regardless of capacity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def nms3x3(resp):
    """Keep values that are the strict max of their 3x3 neighbourhood.

    Response plateaus are tie-broken deterministically: among window pixels
    equal to the window max, only the one with the smallest row-major flat
    index survives, so a plateau emits at most one keypoint per 3x3 window
    (the seed's ``resp >= mx`` emitted one at EVERY plateau pixel).
    Regression: ``tests/test_nms_property.py::test_nms_plateau_tiebreak``.
    """
    win = (1,) * (resp.ndim - 2) + (3, 3)
    strides = (1,) * resp.ndim
    mx = lax.reduce_window(resp, -jnp.inf, lax.max, win, strides, "SAME")
    h, w = resp.shape[-2:]
    idx = (jnp.arange(h)[:, None] * w + jnp.arange(w)[None, :]).astype(
        jnp.int32)
    idx = jnp.broadcast_to(idx, resp.shape)
    sentinel = jnp.iinfo(jnp.int32).max
    # candidate = own index where the pixel attains its window max; the
    # window-min over candidates is the canonical (smallest-index) claimant
    cand = jnp.where(resp >= mx, idx, sentinel)
    min_idx = lax.reduce_window(cand, sentinel, lax.min, win, strides, "SAME")
    return jnp.where((resp >= mx) & (idx == min_idx), resp, 0.0)


def interior_mask(shape_hw, halo: int, valid_h, valid_w):
    """Ownership mask: only interior (non-halo) pixels within the valid
    extent of the tile (edge tiles are padded) emit features."""
    h, w = shape_hw
    ys = jnp.arange(h)
    xs = jnp.arange(w)
    my = (ys >= halo) & (ys < halo + valid_h)
    mx = (xs >= halo) & (xs < halo + valid_w)
    return my[:, None] & mx[None, :]


def count_above(resp, threshold, mask):
    """Exact feature count on the dense map (paper Table 2 analogue)."""
    return jnp.sum(((resp > threshold) & mask).astype(jnp.int32))


def topk_keypoints(resp, k: int, threshold, mask):
    """Select up to K strongest responses.

    Returns (ys [K], xs [K], scores [K], valid [K]) — fixed shapes; invalid
    slots have score 0, coordinates 0 and valid=False.  Ties broken by flat index so the
    selection is deterministic and partition-invariant.
    """
    h, w = resp.shape[-2:]
    flat = jnp.where(mask & (resp > threshold), resp, -jnp.inf).reshape(
        *resp.shape[:-2], h * w)
    scores, idx = lax.top_k(flat, k)
    valid = jnp.isfinite(scores)
    # invalid slots tie at -inf, and a backend may order ties as it likes
    # (TPU top_k does not keep index order among them): zero their payload
    # so results never depend on that order
    scores = jnp.where(valid, scores, 0.0)
    ys = jnp.where(valid, idx // w, 0).astype(jnp.int32)
    xs = jnp.where(valid, idx % w, 0).astype(jnp.int32)
    return ys, xs, scores, valid


def merge_topk(scores_a, payload_a, scores_b, payload_b, k: int):
    """Merge two top-K sets (the 'shuffle' step of global reduction)."""
    scores = jnp.concatenate([scores_a, scores_b], axis=-1)
    top, idx = lax.top_k(scores, k)
    payload = jax.tree_util.tree_map(
        lambda a, b: jnp.take_along_axis(
            jnp.concatenate([a, b], axis=-1), idx, axis=-1),
        payload_a, payload_b)
    return top, payload

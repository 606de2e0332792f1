"""DIFET execution engine: the paper's map/shuffle/reduce on a TPU mesh.

Paper (Hadoop)                      Here (SPMD)
--------------                      -----------------------------------------
HIB bundle in HDFS                  TileBundle sharded over the `data` axis
mapper per image                    vmapped per-tile extractor, jit-compiled
  (decode→gray→detect→describe)       (detect → NMS → top-K → describe)
shuffle                             implicit resharding of per-tile results
reduce (collect outputs)            psum of counts + global top-K merge

The per-tile map needs no cross-tile communication (the paper's "good
locality" of LIFs); the only collectives are the final count all-reduce and
the top-K gather — which is why the workload scales out near-linearly
(Table 1) and why we reproduce that with a collective-light schedule.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.difet_paper import DifetConfig
from repro.core import detectors as D
from repro.core import descriptors as DS
from repro.core import nms


class AlgorithmSpec(NamedTuple):
    """One detector/descriptor algorithm as the engine consumes it.

    Fields:
        response:  ``(img [H,W], cfg, use_pallas) -> [H,W]`` dense
            per-pixel response map (algorithms sharing a response
            function share its computation, see `extract_tile_multi`).
        describe:  ``(img [H,W], ys [K], xs [K]) -> [K, D]`` descriptor
            extractor, or ``None`` for detector-only algorithms.
        threshold: ``cfg -> float`` absolute response threshold applied
            to the dense map before counting/top-K selection.
    """
    response: Callable
    describe: Optional[Callable]
    threshold: Callable


def _harris_resp(img, cfg, use_pallas):
    return D.harris_response(img, k=cfg.harris_k, use_pallas=use_pallas)


def _shi_tomasi_resp(img, cfg, use_pallas):
    return D.shi_tomasi_response(img, use_pallas=use_pallas)


def _fast_resp(img, cfg, use_pallas):
    return D.fast_score(img, threshold=cfg.fast_threshold, arc=cfg.fast_arc,
                        use_pallas=use_pallas)


def _sift_resp(img, cfg, use_pallas):
    # octave-0 (full-res) extrema map drives keypoints.  OpenCV divides the
    # nominal contrast threshold by scales_per_octave — mirror that.
    # Routed through the fused scale-space path: one fused octave
    # computation (a single Pallas DMA on TPU) instead of a per-level
    # pyramid.
    return D.sift_dog_response(
        img, cfg.n_octaves, cfg.scales_per_octave,
        cfg.sift_contrast_threshold / cfg.scales_per_octave,
        use_pallas=use_pallas)[0]


def _surf_resp(img, cfg, use_pallas):
    return D.surf_hessian_response(img, use_pallas=use_pallas)


# paper thresholds are on 8-bit images; ours are [0,1] — rescale where the
# response is quadratic in intensity (hessian/structure-tensor) vs linear.
ALGORITHMS: Dict[str, AlgorithmSpec] = {
    "harris": AlgorithmSpec(_harris_resp, None,
                            lambda c: c.harris_threshold * 1e-4),
    "shi_tomasi": AlgorithmSpec(_shi_tomasi_resp, None,
                                lambda c: c.shi_tomasi_threshold * 1e-2),
    "sift": AlgorithmSpec(_sift_resp, DS.sift_descriptors,
                          lambda c: c.sift_contrast_threshold
                          / c.scales_per_octave),
    "surf": AlgorithmSpec(_surf_resp, DS.surf_descriptors,
                          lambda c: c.surf_hessian_threshold / 255.0 ** 2),
    "fast": AlgorithmSpec(_fast_resp, None, lambda c: 0.0),
    "brief": AlgorithmSpec(_fast_resp, DS.brief_descriptors,
                           lambda c: 0.0),
    "orb": AlgorithmSpec(_fast_resp, DS.orb_descriptors, lambda c: 0.0),
}


def normalize_algorithms(spec) -> tuple:
    """Canonicalize an algorithm selection: accepts a comma-separated string
    or a sequence of names, strips whitespace, drops duplicates (first
    occurrence wins), and rejects unknown names with the valid choices
    spelled out.  Shared by the CLI drivers and the serving API so both
    fail the same way."""
    names = spec.split(",") if isinstance(spec, str) else list(spec)
    valid = ", ".join(sorted(ALGORITHMS))
    out = []
    for raw in names:
        name = raw.strip()
        if not name:
            continue
        if name not in ALGORITHMS:
            raise ValueError(
                f"unknown algorithm {name!r}; valid choices: {valid}")
        if name not in out:
            out.append(name)
    if not out:
        raise ValueError(f"no algorithms selected; valid choices: {valid}")
    return tuple(out)


def _response(spec: AlgorithmSpec, cfg: DifetConfig, tile, use_pallas):
    """The dense response map under the named scope
    ``difet.response.<fn>`` (``_fast_resp`` -> ``difet.response.fast``): one
    scope per distinct response function, so a shared map is named once."""
    fn = spec.response
    with jax.named_scope(
            "difet.response." + fn.__name__.strip("_").removesuffix("_resp")):
        return fn(tile, cfg, use_pallas)


def _select_and_describe(algorithm: str, cfg: DifetConfig, tile, header,
                         resp):
    """NMS → capacity-K selection → describe, given a precomputed response
    map.  Factored out of ``extract_tile`` so algorithms sharing a response
    (fast/brief/orb all use the FAST score) compute it once.  Each stage
    runs under the named scope ``difet.<algorithm>/<stage>`` (``nms``,
    ``topk``, ``describe``), which names its device operations in a
    profiler trace; scopes change op metadata only, never the program."""
    spec = ALGORITHMS[algorithm]
    thr = spec.threshold(cfg)
    k = cfg.max_keypoints_per_tile
    with jax.named_scope(f"difet.{algorithm}"):
        with jax.named_scope("nms"):
            valid_h, valid_w = header[3], header[4]
            not_pad = header[5] == 0
            mask = nms.interior_mask(resp.shape, cfg.halo, valid_h,
                                     valid_w) & not_pad
            count = nms.count_above(resp, thr, mask)
            resp_nms = nms.nms3x3(resp)
        with jax.named_scope("topk"):
            ys, xs, scores, valid = nms.topk_keypoints(resp_nms, k, thr,
                                                       mask)
            out = {"count": count, "scores": scores, "valid": valid}
            # global scene coordinates (interior-relative)
            out["ys"] = header[1] * cfg.tile + (ys - cfg.halo)
            out["xs"] = header[2] * cfg.tile + (xs - cfg.halo)
        if spec.describe is not None:
            with jax.named_scope("describe"):
                desc = spec.describe(tile, ys, xs)
                out["desc"] = jnp.where(valid[:, None], desc,
                                        jnp.zeros_like(desc))
    return out


def extract_tile(algorithm: str, cfg: DifetConfig, tile, header,
                 use_pallas: bool = False):
    """The DIFET 'map function' for one tile (cf. the paper's pseudo-code:
    convert → grayscale → detect → describe → emit).  Returns a dict of
    fixed-shape features."""
    resp = _response(ALGORITHMS[algorithm], cfg, tile, use_pallas)
    return _select_and_describe(algorithm, cfg, tile, header, resp)


def extract_tile_multi(algorithms, cfg: DifetConfig, tile, header,
                       use_pallas: bool = False):
    """Per-tile map for several algorithms at once, computing each distinct
    response function ONCE: ``fast``/``brief``/``orb`` share the FAST score
    map instead of recomputing it thrice.  Returns {algorithm: features}."""
    resp_cache = {}
    out = {}
    for alg in algorithms:
        spec = ALGORITHMS[alg]
        if spec.response not in resp_cache:
            resp_cache[spec.response] = _response(spec, cfg, tile,
                                                  use_pallas)
        out[alg] = _select_and_describe(alg, cfg, tile, header,
                                        resp_cache[spec.response])
    return out


def _reduce_features(algorithm: str, per_tile):
    """The reduce: total count all-reduce + global top-K merge, under the
    named scope ``difet.<algorithm>/reduce``."""
    with jax.named_scope(f"difet.{algorithm}"), jax.named_scope("reduce"):
        total = per_tile["count"].sum()
        t, k = per_tile["scores"].shape
        flat_scores = per_tile["scores"].reshape(t * k)
        flat_valid = per_tile["valid"].reshape(t * k)
        masked = jnp.where(flat_valid, flat_scores, -jnp.inf)
        top_scores, idx = jax.lax.top_k(masked, min(k * 4, t * k))
        top_valid = jnp.isfinite(top_scores)

        def gather(a):
            # invalid picks tie at -inf in whatever order the backend's top_k
            # leaves them: zero their payload so it never depends on that order
            g = jnp.take(a.reshape(t * k, *a.shape[2:]), idx, axis=0)
            return jnp.where(top_valid.reshape(-1, *[1] * (g.ndim - 1)), g,
                             jnp.zeros_like(g))

        result = {
            "total_count": total,
            "per_tile_count": per_tile["count"],
            "top_scores": jnp.where(top_valid, top_scores, 0.0),
            "top_ys": gather(per_tile["ys"]),
            "top_xs": gather(per_tile["xs"]),
            "top_valid": gather(per_tile["valid"]),
            "keypoint_count": per_tile["valid"].sum(),
        }
        if "desc" in per_tile:
            result["top_desc"] = gather(per_tile["desc"])
        return result


def extract_features(bundle_tiles, bundle_headers, algorithm: str,
                     cfg: DifetConfig, use_pallas: bool = False):
    """vmapped map over tiles + the reduce: total count and global top-K."""
    per_tile = jax.vmap(
        functools.partial(extract_tile, algorithm, cfg,
                          use_pallas=use_pallas))(
        bundle_tiles, bundle_headers)
    return _reduce_features(algorithm, per_tile)


def map_tiles_multi(bundle_tiles, bundle_headers, algorithms,
                    cfg: DifetConfig, use_pallas: bool = False):
    """The map alone: per-tile features of every algorithm,
    {algorithm: {key: [N, ...]}} — no cross-tile work, so a mesh can run
    it per device shard (`core/job.py::DifetJob` wraps it in
    ``shard_map``)."""
    return jax.vmap(functools.partial(extract_tile_multi, tuple(algorithms),
                                      cfg, use_pallas=use_pallas))(
        bundle_tiles, bundle_headers)


def reduce_features_multi(per_tile):
    """The reduce of `map_tiles_multi`'s output, per algorithm."""
    return {alg: _reduce_features(alg, r) for alg, r in per_tile.items()}


def extract_features_multi(bundle_tiles, bundle_headers, algorithms,
                           cfg: DifetConfig, use_pallas: bool = False):
    """Multi-algorithm extraction with shared response maps: one vmapped map
    computes every requested algorithm per tile (fast/brief/orb reuse a
    single FAST score), then each algorithm gets its own reduce.  Returns
    {algorithm: result} with per-algorithm results identical to
    ``extract_features`` (same ops on the same inputs)."""
    return reduce_features_multi(map_tiles_multi(
        bundle_tiles, bundle_headers, algorithms, cfg, use_pallas))


def extract_request_features(bundle_tiles, bundle_headers, algorithms,
                             cfg: DifetConfig, use_pallas: bool = False):
    """Serving-path extraction: per-REQUEST results at batch shape.

    ``extract_features_multi`` reduces across the whole batch (one job, many
    tiles); here every batch row is an independent service request, so the
    reduce runs per tile over its own [1, K] candidate set.  Per-tile values
    are batch-invariant — each row runs the same elementwise program
    regardless of its neighbours or position — so a request's result is
    bit-identical to a direct single-tile ``extract_features_multi`` call no
    matter which batch the scheduler rode it in (asserted by
    ``tests/test_serve.py::test_served_parity`` and
    ``test_served_parity_per_algorithm``)."""
    algorithms = tuple(algorithms)
    per_tile = jax.vmap(
        functools.partial(extract_tile_multi, algorithms, cfg,
                          use_pallas=use_pallas))(
        bundle_tiles, bundle_headers)

    def _single(alg, tree):
        return _reduce_features(
            alg, jax.tree_util.tree_map(lambda a: a[None], tree))

    return {alg: jax.vmap(functools.partial(_single, alg))(per_tile[alg])
            for alg in algorithms}


def make_serve_step(algorithms, cfg: DifetConfig, use_pallas: bool = False):
    """jit-compiled serving step for one (shape bucket, algorithm set) pair.
    The scheduler always pads batches to a fixed size, so each pair
    compiles exactly once (`serve/buckets.py::CompileCache`)."""
    return jax.jit(functools.partial(
        extract_request_features, algorithms=tuple(algorithms), cfg=cfg,
        use_pallas=use_pallas))

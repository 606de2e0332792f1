"""DIFET core: the paper's contribution — distributed local-feature
extraction over tile bundles (map/shuffle/reduce on a TPU mesh)."""
from repro.core.bundle import TileBundle, BundleStore, tile_scene, bundle_scenes  # noqa: F401
from repro.core.engine import (  # noqa: F401
    extract_features, extract_features_multi,
    ALGORITHMS,
)
from repro.core.job import DifetJob, JobManifest, ManifestJob  # noqa: F401
from repro.core.matching import (  # noqa: F401
    match_pair, register_pair, estimate_translation, estimate_similarity,
)
from repro.core.mosaic import MatchPhase, solve_layout  # noqa: F401

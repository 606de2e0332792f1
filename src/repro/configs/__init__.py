"""DIFET's configuration: the paper's pipeline settings (`difet_paper.py`)."""
from repro.configs.difet_paper import (  # noqa: F401
    DifetConfig, PAPER_ALGORITHMS, PAPER_CONFIG,
)

"""repro.models - pure-JAX model zoo (scan-over-layers, remat-able)."""
from .common import ArchConfig, Yarn
from .api import Model

__all__ = ["ArchConfig", "Model", "Yarn"]

"""Soft-dependency gating.

TPU-native analogue of ref src/accelerate/utils/imports.py:30-403
(`is_*_available()` probes). The baked-in stack is jax/flax/optax/orbax; torch
is optional interop (CPU weights only), trackers and safetensors are optional.
"""

from __future__ import annotations

import importlib.metadata
import importlib.util
from functools import lru_cache


@lru_cache()
def _package_available(name: str) -> bool:
    return importlib.util.find_spec(name) is not None


def is_torch_available() -> bool:
    return _package_available("torch")


def is_safetensors_available() -> bool:
    return _package_available("safetensors")


def is_transformers_available() -> bool:
    return _package_available("transformers")


def is_datasets_available() -> bool:
    return _package_available("datasets")


def is_tensorboard_available() -> bool:
    return _package_available("tensorboardX") or _package_available("tensorboard")


def is_wandb_available() -> bool:
    return _package_available("wandb")


def is_mlflow_available() -> bool:
    return _package_available("mlflow")


def is_comet_ml_available() -> bool:
    return _package_available("comet_ml")


def is_aim_available() -> bool:
    return _package_available("aim")


def is_clearml_available() -> bool:
    return _package_available("clearml")


def is_dvclive_available() -> bool:
    return _package_available("dvclive")


def is_orbax_available() -> bool:
    return _package_available("orbax")


def is_rich_available() -> bool:
    return _package_available("rich")


def is_pandas_available() -> bool:
    return _package_available("pandas")


def is_tqdm_available() -> bool:
    return _package_available("tqdm")


def is_tpu_available() -> bool:
    """True when a real TPU backend is attached (not the CPU fake)."""
    import jax

    try:
        return jax.devices()[0].platform == "tpu"
    except RuntimeError:
        return False


@lru_cache()
def package_version(name: str) -> str | None:
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return None

"""Plain float64 reference of the descriptor-program op lists that the
``ntx_program`` traffic mixes name, in numpy.

Each value carries a magnitude beside it where the op has a cheap running
error bound (the same op applied to absolute values): fp32 rounding of an
elementwise chain stays within a few units of 2^-24 of that magnitude.
A GEMM carries none; its check normalises by the result's rms instead.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

Value = Tuple[np.ndarray, Optional[np.ndarray]]


def _gemm(env, a, b) -> Value:
    return env[a][0] @ env[b][0], None


def _relu(env, x) -> Value:
    v, mag = env[x]
    return np.maximum(v, 0.0), mag


def _axpy(env, alpha, x, y) -> Value:
    (xv, xm), (yv, ym) = env[x], env[y]
    return alpha * xv + yv, abs(alpha) * xm + ym


def _argmax(env, x) -> Value:
    return np.asarray([np.argmax(env[x][0])], np.float64), None


OPS = {"gemm": _gemm, "relu": _relu, "axpy": _axpy, "argmax": _argmax}


def evaluate(ops, inputs: Dict[str, np.ndarray]) -> Dict[str, Value]:
    """Run ``ops`` (``[kind, out, *args]``, as in the traffic file) over
    named fp32 inputs in float64; returns name -> (value, magnitude)."""
    env: Dict[str, Value] = {}
    for name, arr in inputs.items():
        v = np.asarray(arr, np.float64)
        env[name] = (v, np.abs(v))
    for kind, out, *args in ops:
        env[out] = OPS[kind](env, *args)
    return env


def compare(how: str, got: np.ndarray, want: Value) -> float:
    """One number per output, larger is worse:

    * ``max_err_over_rms``: max |got - want| over rms(want);
    * ``max_err_over_bound``: max |got - want| / magnitude, elementwise;
    * ``mismatches``: count of elements that differ (indices, exact).
    """
    value, mag = want
    got = np.asarray(got, np.float64).reshape(value.shape)
    if how == "max_err_over_rms":
        rms = np.sqrt(np.mean(value ** 2))
        return float(np.max(np.abs(got - value)) / rms)
    if how == "max_err_over_bound":
        return float(np.max(np.abs(got - value) / (mag + 1e-30)))
    if how == "mismatches":
        return float(np.count_nonzero(got != value))
    raise ValueError(f"unknown comparison {how!r}")

"""Registry of SequenceMixer implementations.

One mixer kind == one module implementing the ``SequenceMixer`` protocol and
decorated with ``@register``.  The unified LM (``repro.models.lm``), the
serving engine, the sharding planner and the intensity model all consume
mixers exclusively through ``get_mixer(kind)`` — adding a new kind is a
one-module change plus an import below (or a ``register`` call from anywhere,
e.g. a test or a plugin).
"""
from __future__ import annotations

from typing import Dict, Type

from repro.models.mixers.base import (ArraySpec, CacheSpec, SequenceMixer)

MIXERS: Dict[str, Type[SequenceMixer]] = {}


def register(cls: Type[SequenceMixer]) -> Type[SequenceMixer]:
    """Class decorator: make ``cls`` available as ``get_mixer(cls.kind)``."""
    if not cls.kind:
        raise ValueError(f"{cls.__name__} has no `kind`")
    MIXERS[cls.kind] = cls
    return cls


def get_mixer(kind: str) -> Type[SequenceMixer]:
    try:
        return MIXERS[kind]
    except KeyError:
        raise KeyError(f"unknown mixer kind {kind!r}; registered: "
                       f"{sorted(MIXERS)}") from None


# Built-in kinds self-register on import.
from repro.models.mixers import attn as _attn            # noqa: E402,F401
from repro.models.mixers import gdn as _gdn              # noqa: E402,F401
from repro.models.mixers import gdn_naive as _gdn_naive  # noqa: E402,F401
from repro.models.mixers import ssm as _ssm              # noqa: E402,F401
from repro.models.mixers import rglru as _rglru          # noqa: E402,F401
from repro.models.mixers import qnext_gdn as _qnext_gdn  # noqa: E402,F401
from repro.models.mixers import qnext_attn as _qnext_attn  # noqa: E402,F401
from repro.models.mixers import qnext_moe as _qnext_moe  # noqa: E402,F401

__all__ = ["ArraySpec", "CacheSpec", "SequenceMixer", "MIXERS",
           "register", "get_mixer"]

"""Stage tags on the robust train step, readable on the device trace.

Each stage of one robust step — per-worker backward, worker momentum,
the Byzantine attack, the aggregation, the kappa-hat diagnostic, the
server optimizer and the health taps — is traced inside :func:`stage`.
That puts two marks on every operation the stage lowers to:

* ``jax.named_scope("robust.<stage>")``: the ``op_name`` metadata that HLO
  dumps and xprof's op profile show;
* the frontend attribute ``robust_stage="<stage>"``
  (``jax.experimental.xla_metadata.set_xla_metadata``): it stays on the
  fusions and custom calls XLA builds from the tagged roots, and it is
  part of the HLO text a TPU profile gives each device op, so a trace
  reader can sum device time per stage.

Neither mark changes an instruction: the compiled program, stripped of
``metadata`` and ``frontend_attributes``, is the untagged one.  So the
tags are always on.  A nested stage wins over the stage around it (an
``_opt`` attack's inner aggregation reads ``aggregate``).

The expert layer (``repro.models.moe``) marks its parts the same way
under an attribute of its own, :func:`moe_part`: ``moe_part="route"``
(router product, top-k, dispatch sort, gathers and scatters, combine) and
``moe_part="experts"`` (the held experts' grouped products).  It sits
beside ``robust_stage``, not in its place, so a stage's sum keeps the
expert layer's ops.
"""
from __future__ import annotations

from contextlib import contextmanager
from typing import Iterator

import jax
from jax.experimental.xla_metadata import set_xla_metadata

#: Every stage a tag may name.
STAGES = ("backward", "momentum", "attack", "aggregate", "kappa",
          "optimizer", "taps")
#: The frontend attribute that carries the stage.
ATTRIBUTE = "robust_stage"
#: Every part of the expert layer a tag may name, and its attribute.
MOE_PARTS = ("route", "experts")
MOE_ATTRIBUTE = "moe_part"


@contextmanager
def stage(name: str) -> Iterator[None]:
    """Tag everything traced inside the block as stage ``name``."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages: {STAGES}")
    with jax.named_scope(f"robust.{name}"), \
            set_xla_metadata(**{ATTRIBUTE: name}):
        yield


@contextmanager
def moe_part(name: str) -> Iterator[None]:
    """Tag everything traced inside the block as expert-layer part
    ``name``, beside any stage tag around it."""
    if name not in MOE_PARTS:
        raise ValueError(f"unknown expert-layer part {name!r}; parts: "
                         f"{MOE_PARTS}")
    with jax.named_scope(f"moe.{name}"), \
            set_xla_metadata(**{MOE_ATTRIBUTE: name}):
        yield

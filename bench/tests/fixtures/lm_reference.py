"""A payload fixture for the harness's tests, not a plain reference: its
weights come from the program's own model at ``deepseek_v2_236b.reduced()``
in float32, so that a nested parameter tree, token data and a FLOP count
other than the CNN's go through the harness as it stands. Nothing here
decides ``correct``.

``task`` is the payload factory the tests register as
``repro.core.fixture_lm_task``.
"""

from __future__ import annotations

from typing import Dict

import jax

from repro.configs import deepseek_v2_236b
from repro.core.client import lm_task
from repro.models import Model

CONFIG = deepseek_v2_236b.reduced().replace(param_dtype="float32", dtype="float32")

init_from_key = jax.jit(Model(CONFIG).init)


def task(lr: float, batch_size: int, seq_len: int):
    return lm_task(CONFIG, lr=lr, batch_size=batch_size, seq=seq_len)


def flops_per_example(cfg) -> Dict[str, float]:
    """2 FLOPs per active parameter and token forward, 3x that in an SGD
    step; attention's score FLOPs are left out."""
    forward = 2 * cfg["active_params"] * cfg["seq_len"]
    return {"train": 3 * forward, "eval": forward}

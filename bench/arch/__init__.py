"""Model architectures, one module each, found by the Hugging Face
``model_type`` of a model group: ``bench/arch/<model_type>.py``, and
``llama`` for a group that names none. A module gives what the harness
needs of one group (``group`` is its dict of Hugging Face keys):

* ``model_config(group, name, dtype)``: the program's ``ModelConfig``;
* ``shapes(group)``: leaf name -> shape of the weights
  ``weights.make`` fills;
* ``logits(group, weights, tokens, precision="f32")``: its plain
  float32 reference, ``(T, V)`` logits of one sequence, and with
  ``precision="fp8"`` the control;
* ``decode_tick(group, positions)`` and
  ``prefill_chunk(group, rows, prompt_len)``: the operations and bytes
  of its useful work (``bench/work/``).

So a fleet whose probe and members differ in architecture, or a new
architecture, is added as files.
"""
from __future__ import annotations

import importlib

DEFAULT = "llama"


def of(group: dict):
    """The architecture module of one model group."""
    return importlib.import_module(
        f"bench.arch.{group.get('model_type', DEFAULT)}")

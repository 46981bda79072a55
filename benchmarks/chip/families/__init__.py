"""Architecture families: everything of the benchmark that depends on the
shape of a model's layers, one module per family.

A configuration file names its family under ``"family"`` (``dense`` where
it names none); the runners in ``drive.py`` take every model-dependent
piece from ``families/<family>.py`` and branch on no family. A family
module defines:

* ``model_config(config)``: the program's ``ModelConfig`` with every size
  the file states;
* ``draw_params(config, key)``: the seeded weight pytree, with the
  program's leaf names, shapes and dtypes, drawn one layer at a time
  (traced: ``drive.make_params`` jits it, so the weights are made on the
  device in one call);
* ``Spec`` with ``Spec.from_config(config)``, ``train_reference(params,
  batches, rl, spec, *, prec="f32", half_batch=False)`` and
  ``serve_readings(params, obs, actions, steps, prefix, spec, prec="f32")``:
  the plain float32 reference, its float8 control (``prec="fp8"``) and the
  planted half-batch fault, importing nothing of the program;
* ``seq_shape(config, instruction_tokens)``, ``train_step_flops(config,
  segments, horizon, instruction_tokens)`` and
  ``serve_request_flops(config, instruction_tokens)``: model FLOPs;
* ``kernel_work(config, mix)``: ``{"<kernel>_flops": ..., "<kernel>_bytes":
  ...}`` of one optimizer step (a ``train`` mix) or one answered request
  (a ``serve`` mix); the runners scale it by the window's steps or
  requests into ``Outcome.work``, where a kernel's roofline reader finds it;
* ``reference_programs(config, mix)``: ``(label, jitted fn, argument
  shapes)`` of the reference's device programs, which ``rehearse.py``
  compiles for a described chip.

The parts a new family shares with ``dense`` (RMSNorm, rotary, the GIPO
loss, AdamW, the value head, the float8 control in ``reference.py``;
``causal_pairs`` in ``flops.py``; the truncated-normal draw in
``weights.py``) are imported from there, not copied.
"""
from __future__ import annotations

import importlib.util
import pathlib
import re
from types import ModuleType
from typing import Dict

HERE = pathlib.Path(__file__).resolve().parent
DEFAULT = "dense"
_NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def load(config: Dict, where: pathlib.Path = HERE) -> ModuleType:
    """The family module that ``config`` names; ``LookupError`` where no
    file ``<where>/<family>.py`` holds it."""
    name = config.get("family", DEFAULT)
    path = where / f"{name}.py"
    if not (isinstance(name, str) and _NAME.fullmatch(name)
            and path.is_file()):
        raise LookupError(f"configuration {config.get('name')!r} names "
                          f"family {name!r}, and no file {path} holds it")
    spec = importlib.util.spec_from_file_location(
        "family_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

"""The seam between the runners and a model.

A configuration file carries its source's own ``model_type`` (``"gpt2"``,
``"bert"``); the module ``benchmark/families/<model_type>.py`` holds
everything the runners need to know of that model and nothing else does.
:func:`load` finds it by that name: no key is added to a configuration and
there is no registry.  ``runners/serve.py`` and ``runners/train.py`` keep
what is not a model: the client, the arrivals, the warm-up, the window, the
mesh, the optimizer of the mix, the trace, the sampling, the comparison and
the result.

What a family of a ``"runner": "serve"`` configuration exports (``cfg`` is
the configuration file as a dict; a family may read any key of it, and
reads no key of the mix):

``program_config(cfg)``
    the program's own config object for ``cfg``.
``model(cfg)``
    the flax module the engine gets; ``model.init(key, ids[1, 8])`` gives
    its variables' shapes, and the weights are made from the seed in them
    (``harness.weights.make_like``).
``drawn_vocab(cfg)``
    the traffic draws token ids in ``[4, drawn_vocab(cfg))``: the rows of
    the vocabulary the chip holds that a tokenizer can emit (the published
    vocabulary, or the slice of it the chip's share of a cut model holds).
``page_bytes(cfg, page_size)``
    bytes one page costs in the program's own pool over all layers,
    whatever a layer stores per token; the mix's ``pool_bytes`` over it is
    the number of pages.
``forward_flops_per_token(cfg)``
    matmul FLOPs of one token's forward pass through the parameters that
    token multiplies with (the *active* ones, where experts are routed);
    ``step_mfu.serve`` is built on it.
``judge(cfg, seed, samples, precision="float32")``
    the plain reference over ``samples`` = [(prompt, served tokens)]: makes
    its own weights from the seed (the whole tree, or layer by layer where
    the whole would not fit), and returns ``{"gap", "where", "tokens"}``:
    the widest gap by which a served token's reference logit lies below the
    reference's best, where, and how many tokens were judged.  With a lower
    ``precision`` it judges the token that precision puts first (the
    control).  It runs after the engine has left the device.

What a family of a ``"runner": "train"`` configuration exports (``mix`` is
the cell's workload file):

``program_config(cfg)``, ``model(cfg)``
    as above; ``param_shapes(model)`` gives the parameter tree's shapes.
``grad_step(model)``
    the program's jitted ``(params, batch, step) -> (loss, grads)``.
``batches(cfg, mix, seed, count)``
    ``count`` host batches (dicts of numpy arrays, rows on axis 0, every
    row different) from the seed; the feed ``device_put``s one per step.
``decayed(name)``
    whether the recipe decays the leaf of that name.
``train_flops_per_token(cfg, mix)``
    forward + backward matmul FLOPs per input token; recomputation never
    counts.  ``step_mfu.train`` is built on it.
``follow(cfg, mix, seed, batches, precision="float32")``
    the plain reference's steps over ``batches`` from weights of its own:
    ``{"losses", "grad_norms", "change_norms"}`` as ``compare.train_numbers``
    reads them.  Where the mix gives ``reference_block_rows`` it accumulates
    a batch's gradient over blocks of that many rows, so that a global batch
    fits one chip.  It runs after the program's state has left the device.
``shapes(cfg, mix, chips)``
    what ``flash_roofline.train`` reads: the attention's static shapes on
    ONE chip (the trace's times are averaged over the chips).

What a ``model_config`` PR adds, and it edits nothing:
``benchmark/families/<model_type>.py``; ``benchmark/references/<model>.py``
(imported by the family alone); ``benchmark/configs/<name>.json`` with
``"runner": "serve"`` or ``"train"`` and the source's ``model_type``;
``benchmark/workloads/<cell>.json``; ``benchmark/layer_metrics/*.json`` (and
readers, where none fits); entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib

CONTRACT = {
    "serve": ("program_config", "model", "drawn_vocab", "page_bytes",
              "forward_flops_per_token", "judge"),
    "train": ("program_config", "model", "param_shapes", "grad_step",
              "batches", "decayed", "train_flops_per_token", "follow",
              "shapes"),
}


def load(cfg: dict):
    """The family module of a configuration, by its ``model_type``."""
    return importlib.import_module("benchmark.families." + cfg["model_type"])

"""Adapters: one module per model kind, named by a configuration file's
``adapter``. A model kind the harness has not seen brings one; nothing
else of the harness changes (``manifest.check_modules`` checks, without
importing, that it has what the cell's kind and readers call).

What an adapter has
-------------------
- ``REFERENCE``: the plain reference's module under ``benchmark/reference/``
  (``"reference.dense"``), which has ``logits(config, weights, tokens)``
  for serving and ``loss(...)`` for training.
- ``depth(config, use)``: layers under the use a traffic file names.
- ``model_config(config, use, max_seq_len)``: the program's own model
  configuration for ``LLMConfig(model=...)`` or the train step.
- ``reference_weights(params)``: the program's parameter tree under the
  names the reference uses.
- what the cell's readers say they call (their ``ADAPTER_NEEDS``):
  ``decode_step_bytes`` and ``kv_bytes_per_token`` for the serving
  rooflines, ``train_flops_per_token`` and ``flash_kernel_work`` for the
  training ones; a train cell's kind calls ``train_step``. The shape
  arithmetic imports nothing of the program.

What the serving kinds take from the program (``kinds/serve_common.py``)
------------------------------------------------------------------------
A model that is not a Llama has to keep all four, and its PR has to know:

1. The engine is found as the one ``ray_tpu.llm.engine.LLMEngine`` among the
   process's objects (``_take_engine``), after the window: the served path
   hands out no reference to it. Another engine class, or two engines, and
   the reference check has no weights to run on.
2. The engine must reach its initialiser through the module-level name
   ``ray_tpu.llm.engine.init_params(cfg, key)``, looked up when it is called.
   ``_jitted_init_params`` swaps that name for a jitted copy for the length
   of ``serve.run``; called op by op, as the engine calls it, 3.8B
   parameters took 72 s (my chip run, PR 23). An initialiser bound at
   import (``from ... import init_params`` in another module) is not
   swapped and costs that minute in every run.
3. ``engine.params`` and ``engine.cache`` are dropped by those names after
   ``engine.shutdown()``: the weights go to ``reference_weights``, the cache
   is freed to make room for the float32 reference.
4. ``stats()`` is polled every 100 ms through the serve handle
   (``handle.stats.remote()``) and read once after the window; the
   counters the per-layer metrics name (``layer_metrics/*.json``) and
   ``device_failures``, ``requests_failed`` are keys of it.
"""

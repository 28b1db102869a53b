"""One module per architecture of a pool member, named by the member's
``arch`` key in its configuration file and found as
``bench/arch/<arch>.py`` (``bench.run.arch_module``).  A configuration of
a new architecture is therefore new files only: this module, a reference
under ``bench/reference/`` where none replays it yet, and the
configuration.

Each module defines:

* ``program_config(member) -> repro.models.config.ModelConfig``: the
  program's configuration of the member (``member["config"]`` holds the
  published ``config.json`` values, ``member["name"]`` and
  ``member["source"]`` its name and URL);
* ``make_weights(hf, planting, planted, key) -> dict``: the member's bf16
  weights in the benchmark's own flat layout, made on the device in one
  jitted call (``bench.weights.make``).  The layout always holds
  ``embed``, ``final_norm`` and, for an untied head, ``head``, planted by
  ``bench/weights.py``; the rest is the architecture's own;
* ``to_program(w) -> params``: the same arrays nested as the program's
  parameter tree, with nothing copied;
* ``flops_per_token(hf, context) -> float``: forward FLOPs of one committed
  token with ``context`` keys before it (``mfu`` reads the target's);
* ``published_params(hf) -> int``: the parameter count that the published
  config gives (the tests hold the layout to it);
* ``REFERENCE``: the module under ``bench/reference/`` that replays the
  architecture in float32, with ``hidden(w, hf, tokens, quant=None)`` and
  ``logits(w_out, h, tied, quant=None)``.  The target's is the one that
  decides ``correct``.
"""

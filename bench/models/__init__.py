"""Model modules: what the benchmark knows of one architecture.

A configuration file may name its module with ``"model": "<name>"``; the
harness then loads ``bench/models/<name>.py`` by that name
(``bench.manifest.model``). Without the key the module is ``plain``, the
Llama/Mistral block. A new architecture is a new module here and a new
configuration file; no other file of the benchmark changes.

A module provides:

- ``model_shape(conf) -> dict``: the published sizes (as run) that its
  other functions read, from the configuration file. Every shape holds
  ``hidden_size``, ``vocab_size`` and ``num_hidden_layers``, which the
  shared parts (the embedding, the final norm's gain, the output head,
  the traffic's token ids) read.
- ``LEAF_IDS``: its own leaves' draw ids (``bench.weights.draw``). Ids
  are data: a module never reuses one of ``bench.weights.LEAF_IDS`` or
  of another module, and only appends.
- ``program_config(conf)``: the program's registered config, with only
  what the file reduces changed, after checking that every other size and
  setting is what the file states and what ``layer`` implements.
- ``params_fn(cfg, shape) -> (build, defs)``: ``build(root key)`` makes
  the program's parameter tree from the seeded draws in one jitted call;
  ``defs`` is the program's own tree of definitions.
- The reference's block, in float32 at ``HIGHEST`` precision, and the
  control's rounding (``bench.reference.quantize``) where ``kind`` is
  given: ``layer_weights(root, shape, index, kind) -> dict`` draws
  layer ``index``'s weights again from the seed, ``layer(h, w, shape,
  kind)`` runs that layer over one sequence ``h (S, hidden_size)``, and
  ``final_norm(h, gain, shape)`` is the norm before the output head (its
  gain the shared ``final_norm`` draw).
- Its counts, of what the algorithm needs: ``model_flops(shape, tokens,
  keys, logit_rows)``; the decode attention kernel's
  ``attention_flops(shape, keys)`` and ``decode_attention_bytes(shape,
  keys, tokens)``, with ``ATTENTION_KERNEL``, the pattern of that
  kernel's operation names in a device trace.
"""

"""Plain references, one module per family of architectures, found by
name: a configuration file's ``"reference": "<name>"`` names
``references/<name>.py`` (``bench.spec.reference``).  Everything the
benchmark knows of one family's layers lives in its module; the shared
pieces (the seeded key, the fp8 control's rounding, ``mm``, RMSNorm,
rotary embeddings, causal GQA attention and ``served_gaps``) are in
``bench/reference.py``.  A module gives:

* ``Dims``: a frozen dataclass of the family's sizes with at least
  ``vocab``, ``n_layers``, ``d_model``, ``n_heads``, ``n_kv_heads`` and
  ``head_dim``, and two methods the metric readers call:
  ``trunk_flops()``, the matmul flops of one token through every layer
  (no embedding gather, no head), and ``layer_contexts(ctx)``, the
  context that each paged-attention layer reads for a token at context
  ``ctx``;
* ``model_dims(model)``: its ``Dims`` from a configuration's ``model``
  block (published key names);
* ``weights(key, dims)``: the reference's float32 leaves, from the seed
  alone, the head among them as ``unembed`` [d_model, vocab];
* ``hidden(w, tokens, dims, block, quant)``: the final-normed hidden
  states of one sequence, every matmul through ``reference.mm`` so the
  fp8 control applies;
* ``program_fields(model)``: the program's ``ModelCfg`` fields that the
  ``model`` block sets;
* ``program_params(key, dims, cfg)``: the same leaves in the program's
  parameter layout.
"""

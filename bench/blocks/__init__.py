"""Block modules, one per family of layers, found by the configuration
file's ``"blocks"`` key (``dense`` when it names none) through
``spec.Cell.blocks``.  Each gives:

- ``layout(cfg)``: the shape tree of the program's parameters for ``cfg``
  (tuples of ints at the leaves), refusing a configuration it does not
  describe;
- ``forward(w, tokens, cfg, precision)``: the plain reference, logits
  (L, V) in float32 at ``highest`` for ``precision="f32"``, and its
  ``"fp8"`` control.
"""

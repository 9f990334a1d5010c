"""Plain float32 reference of the served configurations: weights drawn from
the seed without the program (:mod:`.weights`), and one module per block
kind (``dense.py``, named as the configuration's ``model.block``) whose
``served_gaps(model, seed, seqs, *, control=False, device=None)`` checks
served tokens against its forward pass."""

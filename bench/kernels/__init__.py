"""Operations and bytes of each kernel of the served path, from its shapes.

One module per kernel, named as the kernel is; each counts the useful work
of one launch (routed rows, not padded capacity; KV rows inside each slot's
valid prefix), so that a kernel's roofline reads the same work whatever
implements it.  ``model`` counts a whole forward pass per token.
"""

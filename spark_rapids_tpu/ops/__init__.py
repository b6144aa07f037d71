"""Device kernel library: the cuDF-equivalent for the TPU build.

Everything in this package operates on JAX arrays with static shapes
(capacity-bucketed batches, validity/active masks) so XLA compiles each
kernel once per bucket. The reference reaches cuDF through JNI for these
ops (SURVEY.md section 2.4 'implication for the TPU build'); here they are
jit-compiled XLA programs; no hand-written kernel exists.
"""

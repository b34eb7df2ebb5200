"""Pallas kernel subsystem: version-portable fused MC evaluation.

Layout:

* ``pallas_compat`` — the single import point for ``pl``/``pltpu``.
  Owns interpret-mode selection: compiled Mosaic on TPU, the Pallas
  interpreter everywhere else, so the whole subsystem runs (and is
  tested) on CPU-only hosts.
* ``template`` — the shared grid / in-VMEM sampling / accumulator
  scaffolding.  A registered form supplies only an eval body and a param
  packer and gets fused single-family and multi-family kernels for both
  samplers (Threefry MC, digitally-shifted Sobol RQMC).
* ``registry`` — named fast paths with capability metadata (supported
  samplers, max dimension, backends).  ``registry.lookup`` is
  capability-checked: the engine falls back to the chunked pure-JAX path
  for anything a kernel cannot serve, so ``use_kernel=True`` is always
  safe to request.
* ``mc_eval`` — the direct-MC eval kernels: registered forms (harmonic,
  |sum|, gaussian), the pure-jnp oracle, and ``mc_eval.multi`` — fused
  multi-family dispatch that evaluates an entire heterogeneous
  ``MultiFunctionSpec`` in one ``pallas_call`` per (dim, sampler) bucket
  with per-block ``lax.switch`` body selection.
* ``moments`` — the bandwidth-bound stratified-sampling reduction
  (Chan/Welford block merge), built on the same template accumulator.

``use_kernel`` semantics (engine-wide): a request, not a demand — every
family whose registered form supports its (dim, sampler) runs fused;
unregistered or unsupported forms silently take the chunked JAX path
with identical counters, so estimates never depend on which path ran.
"""

"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package ships three modules:

* ``kernel.py`` — the ``pl.pallas_call`` body with explicit BlockSpec
  VMEM tiling; it compiles for the TPU by default, and CPU callers pass
  ``interpret=True`` to run the Pallas interpreter,
* ``ops.py``    — the jit'd public wrapper (padding, GQA folding,
  shape plumbing),
* ``ref.py``    — the pure-jnp oracle the tests sweep against.

Kernels:

* ``flash_attention`` — block-wise online-softmax attention (the LM
  substrate's prefill hot-spot; MXU-aligned 128x128 tiles).
* ``bank_timing``     — the cycle-accurate simulator's per-tick
  eligibility + FR-FCFS select (the paper engine's hot loop, a pure
  VPU workload: elementwise timing legality + masked argmax).
* ``addr_decode``     — batched XOR-folded Skylake address mapping
  (paper Sec. 4 / Fig. 6a) over cache-line indices.
"""

"""Pallas TPU kernels for the paper's compute hot spots.

  simhash        — fused SimHash projection + sign + 32x bit-pack
  window_score   — fused Stars window scoring: similarities, emit mask,
                   counters (the build's hot path)
  leader_score   — fused Stars leader x window similarity + masking
  topk_merge     — per-node top-k degree-slab merge (edge accumulator)
  flash_attention— blocked causal/GQA/sliding-window attention (LM substrate)

Each kernel ships with a jit'd wrapper (ops.py) and a pure-jnp oracle
(ref.py); tests sweep shapes/dtypes and assert allclose vs the oracle with
interpret=True on CPU, and tests/test_tpu_compile.py compiles the graph
path's kernels for a described TPU v5e.
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]

"""Pallas TPU kernels for the perf-critical compute layers, each with a
pure-jnp oracle (ref.py) and a jit'd dispatch wrapper (ops.py). Validated in
interpret mode on CPU; compiled Mosaic on TPU.

This package is also the single authority on *backend selection*
(``policy.py``): :class:`BackendPolicy` names one backend per dispatch path
(fitness / variation / generation / ranking) and validates the names
against each path's ``BACKENDS`` tuple at construction — so a typo'd
backend fails when the ``GAConfig`` is built, not at trace time deep inside
a jit. A backend that cannot compile on the host fails loudly when it is
dispatched: on TPU ``"auto"`` always means the compiled kernel.
"""
from .policy import BACKEND_CHOICES, BackendPolicy, resolve_backends

from .pow2_matmul import pow2_linear, pow2_matmul, pow2_matmul_ref, pack_weights
from .flash_attention import causal_attention, flash_attention, flash_attention_ref
from .pop_mlp import population_correct, pop_mlp_correct, pop_mlp_correct_ref
from .pop_variation import population_variation, pop_variation_kernel, pop_variation_ref
from .pop_generation import population_generation, pop_generation_kernel, pop_generation_jnp
from .pop_ranking import population_ranking, rank_select_rerank, sweep_rank
from .ssd_scan import state_scan, ssd_state_scan, ssd_state_scan_ref


"""Work of one generation of the GA fitness, from shapes alone.

One chromosome scored on one training row of one device instance runs the
integer forward pass of Eq. (4): one masked shift-add term per weight,
Σ fan_in·fan_out terms in all. A term is counted as a multiply-add, two
operations. A generation scores the P children; the initial population
scores P rows once per search.
"""
from __future__ import annotations


def mlp_terms(sizes) -> int:
    """Σ fan_in·fan_out over the layers of topology ``sizes``."""
    return sum(a * b for a, b in zip(sizes, sizes[1:]))


def n_genes(sizes) -> int:
    """Genes of one chromosome: mask, sign and exponent per weight, a
    bias per neuron, a bias shift and an output shift per layer."""
    return sum(3 * a * b + b + 2 for a, b in zip(sizes, sizes[1:]))


def terms(chromosomes: int, rows: int, sizes, k: int = 1) -> int:
    """Shift-add terms of scoring ``chromosomes`` on ``rows`` rows of
    ``k`` device instances."""
    return chromosomes * rows * k * mlp_terms(sizes)


def ops(chromosomes: int, rows: int, sizes, k: int = 1) -> int:
    return 2 * terms(chromosomes, rows, sizes, k)


def generation_bytes(pop: int, rows: int, sizes, k: int = 1) -> int:
    """Least bytes one generation's variation and fitness must move: two
    parent rows read and one child row written per child, its counts
    written, and the training rows (int32 inputs and label) read once."""
    g = n_genes(sizes)
    return 4 * (3 * pop * g + pop * k + rows * (sizes[0] + 1))

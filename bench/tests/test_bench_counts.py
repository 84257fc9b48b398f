"""Work counts and the peaks table."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from counts import pop_generation as work  # noqa: E402
import peaks  # noqa: E402
from reference import ga  # noqa: E402


def test_pendigits_terms_per_generation():
    assert work.terms(256, 7696, (16, 5, 10)) == 256_122_880


def test_cardio_k8_terms_per_generation():
    assert work.terms(256, 1488, (21, 3, 3), k=8) == 219_414_528


def test_ops_count_a_term_as_a_multiply_add():
    assert work.ops(256, 7696, (16, 5, 10)) == 2 * 256_122_880


@pytest.mark.parametrize("sizes", [(16, 5, 10), (21, 3, 3), (11, 4, 7)])
def test_genes_match_the_reference_layout(sizes):
    assert work.n_genes(sizes) == ga.layout(ga.Search(sizes))["n_genes"]


def test_generation_bytes_from_shapes():
    # 3 rows of 226 genes and one count per child, 1,488 rows of 22 words
    assert work.generation_bytes(256, 1488, (21, 3, 3)) == 4 * (
        3 * 256 * 226 + 256 + 1488 * 22)


def test_v5e_peaks():
    p = peaks.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("cpu")

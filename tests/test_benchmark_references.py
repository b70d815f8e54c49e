"""The served families' plain references are the benchmark's own files
(``benchmark/references/``): the family tests import them from there.
What makes a reference one is held here, a case a family: its text names
nothing of the program and asks the matrix products at full precision.
"""

import os

import pytest

_REFERENCES = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "benchmark", "references")


@pytest.mark.parametrize("family", [
    "deepseek_v3", "solar_open2", "xing4", "keye_vl2", "laguna", "axk2",
    "lfm2"])
def test_a_reference_is_independent_and_exact(family):
    with open(os.path.join(_REFERENCES, family + ".py")) as fh:
        text = fh.read()
    assert "flexflow_tpu" not in text
    assert 'precision="highest"' in text

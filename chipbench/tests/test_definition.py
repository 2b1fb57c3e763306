"""NMFk's convergence rule, as each traffic mix states it for the reference
(a fit ends after the chunk of ``chunk`` sweeps that improves its relative
error by less than ``tol``, or at ``nmf_iters``), is the rule the program's
elastic plane applies by default. The window drives the plane at its
defaults; if they drift from the traffic's, the reference no longer
defines the search users get."""
import inspect
import json

import pytest

from .conftest import CHECKOUT

TRAFFIC = sorted(p for p in (CHECKOUT / "chipbench" / "traffic").glob("*.json")
                 if json.loads(p.read_text())["search"] == "nmfk_elastic")


@pytest.mark.parametrize("path", TRAFFIC, ids=[p.stem for p in TRAFFIC])
def test_traffic_rule_is_the_planes_default(path):
    from repro.factorization.planes import NMFkElasticPlane

    defaults = inspect.signature(NMFkElasticPlane).parameters
    traffic = json.loads(path.read_text())
    assert traffic["tol"] == defaults["tol"].default
    assert traffic["chunk"] == defaults["chunk"].default

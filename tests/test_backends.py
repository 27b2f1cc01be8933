"""core.backends registry resolution."""
import pytest

from repro.core.backends import (available_backends, make_backend,
                                 register_backend)
from repro.core.jaxsim import JaxSimPool
from repro.core.profiles import V100_LLAMA2_7B
from repro.core.simulator import Cluster
from repro.core.vecsim import VecCluster, VecSimPool
from repro.serving.gateway import Gateway, GatewayConfig
from repro.serving.policies import make_gateway_policy

PROF = V100_LLAMA2_7B


def test_registry_resolves_all_builtin_backends():
    assert {"py", "vec", "jax", "engine"} <= set(available_backends())
    for name in ("py", "vec", "jax", "engine"):
        assert make_backend(name).name == name


def test_make_backend_unknown_name_lists_alternatives():
    with pytest.raises(ValueError, match="unknown simulator backend"):
        make_backend("cuda")
    with pytest.raises(ValueError, match="vec"):
        make_backend("nope")


def test_register_backend_shadows():
    @register_backend("_test_shadow")
    class Fake:
        name = "_test_shadow"

        def make_cluster(self, profile, n_instances, **kw):
            return "fake"

        def make_pool(self, n_episodes, **kw):
            return "fake-pool"
    try:
        assert make_backend("_test_shadow").make_pool(1) == "fake-pool"
    finally:
        from repro.core import backends as b
        b._REGISTRY.pop("_test_shadow", None)


def test_cluster_kwarg_dispatches_through_registry():
    assert not isinstance(Cluster(PROF, 2), VecCluster)
    cv = Cluster(PROF, 2, backend="vec")
    assert isinstance(cv, VecCluster)
    assert type(cv.pool) is VecSimPool
    cj = Cluster(PROF, 2, backend="jax")
    assert isinstance(cj, VecCluster)
    assert isinstance(cj.pool, JaxSimPool)


def test_pool_less_backends_raise_actionable_errors():
    with pytest.raises(ValueError, match="no pooled"):
        make_backend("py").make_pool(2)
    with pytest.raises(ValueError, match="pooled"):
        make_backend("engine").make_pool(2)
    with pytest.raises(ValueError, match="engines="):
        make_backend("engine").make_cluster(PROF, 2)


def test_gateway_backend_resolves_through_registry():
    gw = Gateway(GatewayConfig(backend="jax"), (PROF,) * 2,
                 make_gateway_policy("jsq"))
    assert isinstance(gw.cluster, VecCluster)
    assert isinstance(gw.cluster.pool, JaxSimPool)



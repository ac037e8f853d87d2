"""``HybridLM`` of the ``glm4_moe_lite`` family under the optimizer and the
estimator: the module's router bias under the balancing rule, and a fit
through the resident scan runner that reports the two loss terms apart (the
comparison with the reference is ``test_glm_hybridlm.py``'s)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import glm_hybrid_model as gm
from glm_hybrid_model import no_persistent_cache  # noqa: F401 - autouse
from glm_hybrid_model import T, V, model
from raydp_tpu.models import hybridlm_optimizer


@pytest.fixture(scope="module")
def batch():
    return gm.batch()


def test_the_modules_router_bias_falls_under_the_balancing_rule(batch):
    """``hybridlm_optimizer`` labels a leaf by its NAME: the module's block
    keeps ``expert_bias``, so the rule steps it by rate x what the layer
    handed back (its excess load), and AdamW does not touch it."""
    m = model()
    p = m.init(jax.random.PRNGKey(0), batch, None, method="loss")
    grads = jax.jit(jax.grad(
        lambda q: m.apply(q, batch, method="loss")[0]))(p)
    tx = hybridlm_optimizer(learning_rate=3e-4, warmup_steps=8,
                            expert_bias_rate=0.05)
    updates, _ = tx.update(grads, tx.init(p), p)
    for name in ("layer_1", "mtp_0"):
        excess = grads["params"][name]["expert_bias"]
        assert float(jnp.abs(excess).max()) > 0
        assert float(jnp.abs(excess.sum())) < 1e-4  # an excess: it sums to 0
        assert bool(jnp.allclose(updates["params"][name]["expert_bias"],
                                 -0.05 * excess, rtol=1e-6, atol=1e-9))


def test_a_fit_reports_the_two_terms_apart():
    """ETL -> store -> exchange -> ``JaxEstimator.fit`` through the resident
    scan runner, no path of its own: the epoch program sums ``mtp_loss``
    beside the experts' report, and ``epoch_facts`` gives the epoch's mean as
    the gauge ``model.mtp.loss``."""
    import pyarrow as pa
    from jax.sharding import Mesh

    import raydp_tpu
    from raydp_tpu import obs
    from raydp_tpu.cluster import api as cluster
    from raydp_tpu.estimator import JaxEstimator

    ids = np.asarray(jax.random.randint(
        jax.random.PRNGKey(5), (8, T + 1), 0, V), np.int32)
    table = pa.table({"tokens": pa.FixedSizeListArray.from_arrays(
        pa.array(ids.ravel()), ids.shape[1])})
    session = raydp_tpu.init_etl("glmlm", num_executors=1, executor_cores=1,
                                 executor_memory="500M")
    try:
        est = JaxEstimator(
            model=model(), loss="model", feature_columns=["tokens"],
            feature_dtype=np.int32, label_column=None, batch_size=2,
            mesh=Mesh(np.array(jax.devices()[:1]), ("data",)), num_epochs=2,
            seed=0, optimizer=hybridlm_optimizer(
                3e-4, warmup_steps=8, expert_bias_rate=0.05))
        history = est.fit_on_etl(session.from_arrow(table, num_partitions=2))
    finally:
        raydp_tpu.stop_etl()
        cluster.shutdown()
    assert est.fit_stats_["runner"] == "resident_scan"
    assert est.fit_stats_["steps"] == 2 * 4
    for record in history:
        report = record["train_report"]
        assert report["expert_load"].shape == (5, 2)
        module = float(report["mtp_loss"]) / 4
        assert 4.0 < module < 7.0, module
        # the history's loss is the main one + 0.3 x the module's
        total = float(record["train_loss"])
        assert 4.0 < total - 0.3 * module < 7.0, (total, module)
    gauge = obs.metrics.snapshot()["model.mtp.loss"]["value"]
    assert gauge == pytest.approx(
        float(history[-1]["train_report"]["mtp_loss"]) / 4)

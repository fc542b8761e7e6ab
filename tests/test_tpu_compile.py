"""Full-width compiles for a described TPU v5e, without the chip.

The chip's compiler is installed here and compiles for a topology that
is described, not attached: these tests lower the main path's device
programs at real width and fail on what the chip's compiler would
refuse (unsupported ops, programs that do not fit HBM). Shapes only —
nothing runs. The policy is chip_smoke.py's: the 8 deploy/policies
templates and 500 constraints.
"""

import numpy as np
import pytest

import chip_smoke as smoke

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    cc.reset_cache()


@pytest.fixture(scope="module")
def policy_state():
    """A TpuDriver holding the smoke's 500 constraints and a few Pods;
    -> (driver, client, constraint set)."""
    from gatekeeper_tpu.constraint import TpuDriver

    templates, _ = smoke.load_policy()
    drv = TpuDriver()
    client = smoke.new_client(
        drv, templates, smoke.make_constraints(templates, 500)
    )
    for i, defect in enumerate((None,) + smoke.DEFECTS):
        client.add_data(smoke.make_pod(i, "team-000", defect))
    with drv._mutex:
        cs = drv._constraint_set(smoke.TARGET)
    assert len(cs.constraints) == 500
    return drv, client, cs


def _shapes(tree, sharding, rows=None):
    """ShapeDtypeStructs for every leaf, the leading axis set to
    `rows` when given."""
    import jax

    def one(x):
        shape = x.shape if rows is None else (rows,) + x.shape[1:]
        return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)

    return jax.tree.map(one, tree)


def _fits(compiled):
    m = compiled.memory_analysis()
    total = (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes
    )
    assert total < V5E_HBM, f"{total} bytes per device: {m}"


def _audit_step(policy_state):
    drv, _, cs = policy_state
    with drv._mutex:
        corpus = drv._audit_corpus(smoke.TARGET)
    fn, args, _ = drv.kernel.prepare(
        cs.programs, cs.ms, corpus.fb_dev, corpus.tok, corpus.g
    )
    return fn, args


def test_audit_step_compiles_full_chunk(topo, no_compile_cache, policy_state):
    """The fused audit step on one full N_CHUNK (32768-row) chunk
    against the 500-constraint stacked set."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from gatekeeper_tpu.constraint.tpudriver import N_CHUNK

    one = SingleDeviceSharding(topo.devices[0])
    fn, (ms, fb, tok, tabs, consts) = _audit_step(policy_state)
    compiled = jax.jit(fn).lower(
        _shapes(ms, one), _shapes(fb, one, N_CHUNK),
        _shapes(tok, one, N_CHUNK), _shapes(tabs, one),
        _shapes(consts, one),
    ).compile()
    _fits(compiled)


def test_sharded_audit_step_compiles_on_four_chips(
    topo, no_compile_cache, policy_state
):
    """The same step over a ("c", "n") mesh of the four described chips
    with c_shards=2: constraints split on "c", resources on "n"."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from gatekeeper_tpu.constraint.tpudriver import N_CHUNK

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("c", "n"))
    fn, (ms, fb, tok, tabs, consts) = _audit_step(policy_state)
    rep = NamedSharding(mesh, P())
    compiled = jax.jit(fn).lower(
        _shapes(ms, NamedSharding(mesh, P("c"))),
        _shapes(fb, NamedSharding(mesh, P("n")), N_CHUNK),
        _shapes(tok, NamedSharding(mesh, P("n")), N_CHUNK),
        _shapes(tabs, rep), _shapes(consts, rep),
    ).compile()
    _fits(compiled)
    assert "all-reduce" in compiled.as_text()  # the per-constraint totals


def test_review_step_compiles_at_admission_bucket(
    topo, no_compile_cache, policy_state
):
    """The fused review sweep the webhook dispatches, for one burst of
    the smoke's admission requests (its batch bucket)."""
    import random

    import jax
    from jax.sharding import SingleDeviceSharding

    from gatekeeper_tpu.constraint.handler import handler_for

    drv, client, cs = policy_state
    scale = smoke.Scale()
    requests = smoke.make_admission_requests(scale, random.Random(0))
    handler = handler_for(client, smoke.TARGET)
    target = client.targets[smoke.TARGET]
    # what Client.warm_review_path hands the driver for one burst
    reviews = [
        target.handle_review(handler.augment_request(r))[1]
        for r in requests[: scale.burst_size]
    ]
    with drv._mutex:
        corpus = drv._ephemeral_corpus(
            smoke.TARGET, cs, reviews, drv._ns_cache(smoke.TARGET),
            coarse_feats=True,
        )
    policy = drv.kernel.stage_policy(cs.programs, cs.ms)
    stacked = drv._stage_corpus(corpus)
    fn = drv.kernel.need_all_fn(
        policy, (corpus.g, corpus.g1), min(1024, stacked.chunk)
    )
    args = drv.kernel.need_all_args(policy, stacked)
    one = SingleDeviceSharding(topo.devices[0])
    compiled = jax.jit(fn).lower(*_shapes(args, one)).compile()
    _fits(compiled)

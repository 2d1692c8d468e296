"""The benchmark's pinned googlenet against the program's: a test that
flags drift (the benchmark keeps its own copy so that a program change
cannot move the yardstick), not a gate on the program."""
import chiptiny


def test_pinned_googlenet_matches_the_programs_config():
    from repro.configs.googlenet import CONFIG
    pinned = chiptiny.reference_module().program_config(
        chiptiny.googlenet_sizes())
    assert pinned.img == CONFIG.img
    assert pinned.stem == CONFIG.stem
    assert pinned.modules == CONFIG.modules
    assert pinned.pool_between == CONFIG.pool_between
    assert pinned.num_classes == CONFIG.num_classes
    assert pinned.param_count() == CONFIG.param_count()


def test_reference_params_fit_the_program_layout():
    import jax
    import jax.numpy as jnp
    from repro.models import cnn
    g = chiptiny.googlenet_sizes()
    ref = chiptiny.reference_module()
    ours = jax.eval_shape(lambda: ref.init_params(g, jnp.zeros(2,
                                                              jnp.uint32)))
    theirs = jax.eval_shape(lambda: cnn.init_params(
        ref.program_config(g), jax.random.PRNGKey(0)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert [x.shape for x in jax.tree.leaves(ours)] == \
        [x.shape for x in jax.tree.leaves(theirs)]

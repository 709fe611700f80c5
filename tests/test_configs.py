import pytest

from trlx_tpu.data.configs import TRLConfig
from trlx_tpu.data.default_configs import (
    default_dpo_config,
    default_grpo_config,
    default_ilql_config,
    default_ppo_config,
    default_rft_config,
    default_sft_config,
)
from trlx_tpu.data.method_configs import ILQLConfig, PPOConfig, get_method


@pytest.mark.parametrize(
    "factory",
    [default_ppo_config, default_ilql_config, default_sft_config,
     default_rft_config, default_grpo_config, default_dpo_config],
)
def test_roundtrip(factory):
    cfg = factory()
    again = TRLConfig.from_dict(cfg.to_dict())
    assert again.to_dict() == cfg.to_dict()


def test_yaml_roundtrip(tmp_path):
    import yaml

    cfg = default_ppo_config()
    p = tmp_path / "cfg.yml"
    p.write_text(yaml.safe_dump(cfg.to_dict()))
    loaded = TRLConfig.load_yaml(str(p))
    assert loaded.method.cliprange == cfg.method.cliprange
    assert loaded.train.batch_size == cfg.train.batch_size


def test_evolve_deep_merge():
    cfg = default_ilql_config()
    new = cfg.evolve(method=dict(gamma=0.5, gen_kwargs=dict(max_new_tokens=7)))
    assert new.method.gamma == 0.5
    assert new.method.gen_kwargs["max_new_tokens"] == 7
    # untouched siblings preserved
    assert new.method.gen_kwargs["top_k"] == cfg.method.gen_kwargs["top_k"]
    assert cfg.method.gamma == 0.99  # original untouched


def test_update_dotted_paths():
    cfg = default_ppo_config()
    new = TRLConfig.update(cfg, {"train.seed": 7, "method.gamma": 0.9})
    assert new.train.seed == 7
    assert new.method.gamma == 0.9


def test_update_unknown_path_raises():
    cfg = default_ppo_config()
    with pytest.raises(ValueError, match="not present"):
        TRLConfig.update(cfg, {"train.does_not_exist": 1})


def test_unknown_section_key_raises():
    d = default_ppo_config().to_dict()
    d["model"]["bogus_key"] = 1
    with pytest.raises(ValueError, match="unknown keys"):
        TRLConfig.from_dict(d)


def test_removed_profile_keys_fail_as_unknown_keys():
    """train.profile_dir/_start/_stop are gone (the one profiler
    trigger is train.obs.profile.*): an old config naming them fails
    like any other unknown key, not silently."""
    d = default_ppo_config().to_dict()
    d["train"]["profile_dir"] = "/tmp/trace"
    with pytest.raises(ValueError, match="unknown keys"):
        TRLConfig.from_dict(d)


def test_method_registry():
    assert get_method("ppoconfig") is PPOConfig
    assert get_method("ILQLConfig") is ILQLConfig
    with pytest.raises(ValueError):
        get_method("nope")


def test_mesh_defaults():
    cfg = default_ppo_config()
    assert cfg.train.mesh == {"dp": -1, "fsdp": 1, "tp": 1, "sp": 1}


def test_method_loss_delegates_match_ops():
    """PPOConfig.loss / .get_advantages_and_returns and ILQLConfig.loss are
    thin hyperparameter-binding facades over ops/{ppo,ilql}.py — assert they
    produce the exact op outputs (they are public API surface, reference
    modeling_ppo.py:136-238, modeling_ilql.py:94-166)."""
    import jax.numpy as jnp
    import numpy as np

    from trlx_tpu.data import ILQLBatch
    from trlx_tpu.ops.ilql import ilql_loss
    from trlx_tpu.ops.ppo import gae_advantages_and_returns, ppo_loss

    rng = np.random.default_rng(0)
    B, T = 3, 6
    f32 = lambda *s: jnp.array(rng.normal(size=s).astype(np.float32))

    mcfg = PPOConfig(
        name="PPOConfig", cliprange=0.15, cliprange_value=0.25, vf_coef=0.7, gamma=0.9, lam=0.8
    )
    values, rewards = f32(B, T), f32(B, T)
    adv_c, ret_c = mcfg.get_advantages_and_returns(values, rewards, T)
    adv_o, ret_o = gae_advantages_and_returns(values, rewards, gamma=0.9, lam=0.8)
    np.testing.assert_array_equal(np.asarray(adv_c), np.asarray(adv_o))
    np.testing.assert_array_equal(np.asarray(ret_c), np.asarray(ret_o))

    lp, v, olp, ov = f32(B, T), f32(B, T), f32(B, T), f32(B, T)
    mask = jnp.ones((B, T), jnp.float32)
    loss_c, stats_c = mcfg.loss(lp, v, olp, ov, adv_o, ret_o, mask)
    loss_o, stats_o = ppo_loss(
        lp, v, olp, ov, adv_o, ret_o, mask,
        cliprange=0.15, cliprange_value=0.25, vf_coef=0.7,
    )
    assert float(loss_c) == float(loss_o)
    assert set(stats_c) == set(stats_o)
    for k in stats_o:
        np.testing.assert_array_equal(np.asarray(stats_c[k]), np.asarray(stats_o[k]))

    V, n_actions, n_states = 11, 4, 5
    icfg = ILQLConfig(
        name="ILQLConfig", tau=0.6, gamma=0.95, cql_scale=0.2, awac_scale=0.5, beta=0.1
    )
    qs = [f32(B, n_actions, V) for _ in range(2)]
    tqs = [q + 0.1 for q in qs]
    vs = f32(B, n_states, 1)
    logits = f32(B, n_actions, V)
    batch = ILQLBatch(
        input_ids=jnp.array(rng.integers(0, V, size=(B, T))),
        attention_mask=jnp.ones((B, T), jnp.int32),
        rewards=f32(B, n_actions),
        states_ixs=jnp.array(rng.integers(0, T - 1, size=(B, n_states))),
        actions_ixs=jnp.array(np.sort(rng.integers(0, T - 1, size=(B, n_actions)), axis=-1)),
        dones=jnp.ones((B, n_states), jnp.int32),
    )
    loss_c, stats_c = icfg.loss((logits, (qs, tqs, vs)), batch)
    loss_o, stats_o = ilql_loss(
        logits, qs, tqs, vs, batch,
        tau=0.6, gamma=0.95, cql_scale=0.2, awac_scale=0.5, beta=0.1, two_qs=True,
    )
    assert float(loss_c) == float(loss_o)
    assert set(stats_c) == set(stats_o)
    for k in stats_o:
        np.testing.assert_array_equal(np.asarray(stats_c[k]), np.asarray(stats_o[k]))


# ---------------------------------------------------------------------------
# registry invariants (ISSUE 9 satellite)
# ---------------------------------------------------------------------------


def test_duplicate_trainer_registration_raises():
    """register_trainer must refuse to silently overwrite an existing
    name — two trainers shadowing each other under one key was a latent
    registry footgun."""
    from trlx_tpu.trainer import register_trainer
    from trlx_tpu.utils.loading import get_trainer

    get_trainer("TPUPPOTrainer")  # ensure the registry is populated
    with pytest.raises(ValueError, match="already registered"):

        @register_trainer("TPUPPOTrainer")
        class NotPPO:  # pragma: no cover - never constructed
            pass

    # the original registration survived the refused overwrite
    assert get_trainer("TPUPPOTrainer").__name__ == "TPUPPOTrainer"


def test_duplicate_method_registration_raises():
    from trlx_tpu.data.method_configs import register_method

    with pytest.raises(ValueError, match="already registered"):

        @register_method("PPOConfig")
        class NotPPOConfig:  # pragma: no cover - never constructed
            pass

    assert get_method("PPOConfig") is PPOConfig


def test_registry_trainer_method_default_config_consistency():
    """Every registered trainer has a matching default_*_config entry
    whose method config resolves through the method registry — the
    three registries (trainers, method configs, programmatic defaults)
    cannot drift apart as the algorithm matrix grows."""
    import trlx_tpu.data.default_configs as dc
    import trlx_tpu.data.method_configs as mc
    import trlx_tpu.trainer as trainer_pkg
    from trlx_tpu.utils.loading import get_trainer

    get_trainer("TPUPPOTrainer")  # import side effects populate registry
    defaults = {
        name: getattr(dc, name)()
        for name in dir(dc)
        if name.startswith("default_") and name.endswith("_config")
    }
    assert len(defaults) >= 6  # ppo/ilql/sft/rft/grpo/dpo
    by_trainer = {}
    for name, cfg in defaults.items():
        key = cfg.train.trainer.lower()
        assert key not in by_trainer, (
            f"{name} and {by_trainer[key][0]} both target {key}"
        )
        by_trainer[key] = (name, cfg)
    # every registered trainer <- exactly one default config
    assert set(by_trainer) == set(trainer_pkg._TRAINERS), (
        "trainer registry and default_*_config entries drifted: "
        f"defaults={sorted(by_trainer)} registered="
        f"{sorted(trainer_pkg._TRAINERS)}"
    )
    for key, (name, cfg) in sorted(by_trainer.items()):
        # the method config is registered and its name key resolves
        # back to the exact class the default constructed
        assert mc.get_method(cfg.method.name) is type(cfg.method), name
        # and the trainer class actually constructs with this method
        # type (the trainer-side isinstance gate names the same class)
        assert type(cfg.method).__name__.lower() in mc._METHODS

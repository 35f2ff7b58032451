import dataclasses

import pytest

import arroyo_tpu.config as cfg_mod
from arroyo_tpu.config import Config, load_config, parse_duration, parse_size, update


def test_defaults():
    c = Config()
    assert c.pipeline.source_batch_size == 512
    assert c.pipeline.checkpointing.interval == 10.0


def test_parse_duration_and_size():
    assert parse_duration("10ms") == 0.01
    assert parse_duration("5s") == 5.0
    assert parse_duration("2m") == 120.0
    assert parse_size("64KB") == 64_000
    assert parse_size("1MiB") == 2**20


def test_env_overrides():
    c = load_config(environ={
        "ARROYO__PIPELINE__SOURCE_BATCH_SIZE": "32",
        "ARROYO__PIPELINE__CHECKPOINTING__INTERVAL": "250ms",
        "ARROYO__TPU__ENABLED": "false",
    })
    assert c.pipeline.source_batch_size == 32
    assert c.pipeline.checkpointing.interval == 0.25
    assert c.tpu.enabled is False


def test_yaml_file(tmp_path):
    f = tmp_path / "arroyo.yaml"
    f.write_text("pipeline:\n  queue_size: 7\n  checkpointing:\n    interval: 1s\n")
    c = load_config(str(f), environ={})
    assert c.pipeline.queue_size == 7
    assert c.pipeline.checkpointing.interval == 1.0


def test_scoped_update():
    base = cfg_mod.config().pipeline.source_batch_size
    with update(pipeline={"source_batch_size": 9}):
        assert cfg_mod.config().pipeline.source_batch_size == 9
    assert cfg_mod.config().pipeline.source_batch_size == base


def leaves(node) -> int:
    if not dataclasses.is_dataclass(node):
        return 1
    return sum(leaves(getattr(node, f.name))
               for f in dataclasses.fields(node))


@pytest.mark.parametrize("section, key, value", [
    ("engine", "pipeline_depth", 2),
    ("engine", "segment_donation", "on"),
    ("tpu", "device_join_force", True),
])
def test_an_option_that_went_is_an_unknown_key(section, key, value):
    """The fused segment's staging depth and donation went with the
    tiers they steered, the join's force flag with the rule every other
    operator uses (`tpu.enabled` + `tpu.require_accelerator`): a file
    that still sets one is told so, not ignored."""
    before = leaves(cfg_mod.config())
    with pytest.raises(ValueError, match=f"unknown config key: {key}"):
        with update(**{section: {key: value}}):
            pass
    assert leaves(cfg_mod.config()) == before == leaves(Config()) == 148

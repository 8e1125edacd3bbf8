"""Every example YAML must parse through the config loader, and name only
options the program still has."""

import functools
import glob
import pathlib
import re

import pytest

from automodel_tpu.config.loader import load_config
from automodel_tpu.models.common.backend import BackendConfig

EXAMPLES = sorted(glob.glob("examples/**/*.yaml", recursive=True))


@functools.cache
def _program_source():
    return "\n".join(p.read_text() for p in sorted(pathlib.Path("automodel_tpu").rglob("*.py")))


def _example_id(path):
    return path.split("examples/")[-1]


@pytest.mark.parametrize("path", EXAMPLES, ids=_example_id)
def test_example_parses(path):
    cfg = load_config(path)
    assert cfg.get("model") is not None or cfg.get("dataset") is not None


@pytest.mark.parametrize("path", EXAMPLES, ids=_example_id)
def test_example_names_only_options_the_program_has(path):
    """A stale option, not a YAML error: the ``backend:`` block builds the way
    the recipe builds it (an unknown field, enum or remat rung raises), and every
    top-level key is one some module of ``automodel_tpu/`` reads by name, so an
    option that left the program cannot stay behind in an example, ignored."""
    cfg = load_config(path).to_dict()
    BackendConfig(**(cfg.get("backend") or {}))
    unread = [k for k in cfg
              if not re.search(rf"""["']{re.escape(k)}[."']""", _program_source())]
    assert not unread, f"{path}: no module of automodel_tpu/ reads {unread}"


@pytest.mark.parametrize("rung", ["dots_no_batch", "mlp_gate_attn"])
def test_a_remat_rung_that_left_fails_at_construction(rung):
    """Not at the first ``layer_remat``: the check above leans on that."""
    with pytest.raises(ValueError, match="unknown remat_policy"):
        BackendConfig(remat_policy=rung)

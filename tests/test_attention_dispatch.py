"""One owner of attention's choices (``models/attention.py``): the cache of
every attention layer holds the window that layer attends over, the
server's dispatch report follows each layer's own window, and the server
keys its derived steps by every field of the configs they are derived
from."""
import dataclasses

import pytest

from repro.configs.registry import ARCHS, smoke_config
from repro.launch.serve import DecodeServer
from repro.models.attention import ATTENTION_KINDS, layer_window
from repro.models.model import cache_specs
from repro.parallel.sharding import KernelConfig, ParallelConfig

CAP = 64
FLASH = KernelConfig(use_flash=True, flash_block_q=16, flash_block_kv=16)


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_attention_cache_follows_the_layer_window(arch):
    """Each attention layer's cache holds ``CAP`` slots, or its window's
    where ``layer_window`` gives it one (the smoke window, 16, is shorter
    than ``CAP``). The prefill reports the Pallas flash kernel, at blocks
    that tile the prompt, only where every attention layer is global GQA:
    a windowed layer, MLA, or no attention at all runs pure JAX."""
    cfg = smoke_config(arch)
    windows = set()
    segments = cache_specs(cfg, 2, CAP)["segments"]
    for (_, cycle), seg in zip(cfg.pattern_layers(), segments):
        for j, kind in enumerate(cycle):
            if kind not in ATTENTION_KINDS:
                continue
            window = layer_window(cfg, kind)
            windows.add(window)
            leaves = seg[f"{j}:{kind}"]
            slots = leaves["pos"].shape[-1]
            assert slots == (min(CAP, window) if window else CAP)
            for name in ("k", "v", "latent"):
                if name in leaves:
                    assert leaves[name].shape[-2] == slots
    server = DecodeServer(cfg, ParallelConfig(kernel=FLASH), batch=1,
                          prompt_len=32, decode_steps=4)
    flash = windows == {None} and cfg.attention != "mla"
    assert server.prefill_dispatch == ("pallas" if flash else "jax")


RULES = ("param_rules", "act_rules")
#: a value other than the default, for fields whose default is None
OTHER = {"capacity_factor": 1.25, "interpret": True}


def _other(name, value):
    if name in OTHER:
        return OTHER[name]
    if isinstance(value, KernelConfig):
        return None
    if isinstance(value, bool):
        return not value
    if isinstance(value, (int, float)):
        return value + 1
    return value + "_other"


BASE = ParallelConfig(kernel=KernelConfig())
FIELDS = ([("pcfg", f.name) for f in dataclasses.fields(ParallelConfig)
           if f.name not in RULES]
          + [("kernel", f.name) for f in dataclasses.fields(KernelConfig)])


@pytest.mark.parametrize("owner,name", FIELDS,
                         ids=[f"{o}.{n}" for o, n in FIELDS])
def test_step_key_tells_apart_configs_one_field_apart(owner, name):
    """Two configs that differ in any one field but the rule tables derive
    different steps, so they never share a compiled step."""
    if owner == "pcfg":
        changed = BASE.replace(
            **{name: _other(name, getattr(BASE, name))})
    else:
        changed = BASE.replace(kernel=BASE.kernel.replace(
            **{name: _other(name, getattr(BASE.kernel, name))}))
    assert changed != BASE
    assert DecodeServer._stepfn_key(changed) != DecodeServer._stepfn_key(BASE)

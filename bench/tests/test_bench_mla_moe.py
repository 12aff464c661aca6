"""The MLA + MoE serve cell's reference, driver, FLOP counts and readers, on
the CPU at a small size: the program through ``DecodeServer`` (prefill,
then decode through its latent cache with the Pallas kernel in interpret
mode) against the reference's full forward."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, flops, flops_mla_moe, harness
from bench import weights_mla_moe as W
from bench.drivers import serve_mla_moe as drv
from bench.reference import mla_moe

SEED = 2**33 + 15
PROMPT, STEPS, B = 12, 6, 2


def small(dtype="float32", **kw):
    """DeepSeek-V3's configuration file at smoke widths: 16 experts in 4
    groups of which 2 are picked, 4 held from the fifth on, YaRN on."""
    with open(os.path.join(harness.BENCH, "configs",
                           "deepseek-v3.json")) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
               moe_intermediate_size=32, n_group=4, topk_group=2,
               routed_experts_published=16, n_routed_experts=4,
               first_expert_held=4, num_experts_per_tok=4,
               num_hidden_layers=3, first_k_dense_replace=1, vocab_size=256,
               torch_dtype=dtype)
    cfg.update(kw)
    return cfg


class Ctx:
    def __init__(self, cfg):
        self.config, self.config_name = cfg, "small"
        self.traffic = {"loop": "closed", "clients": B,
                        "prompt_tokens": PROMPT, "output_tokens": STEPS}
        self.workload = {
            "kernels": {"decode": {"block_kv": 8, "num_splits": 2,
                                   "combine": "jax"}},
            "prefill": {"flash_threshold": 8, "block_kv": 4}}


def served(cfg):
    """(prompts, served tokens, the program's logits at each served
    position) of one batch through the server, and the routed copies
    its held experts computed in the decode steps."""
    server, traffic = drv.build_server(Ctx(cfg), SEED)
    prompts = traffic.prompts(0)
    logits, cache = server.prefill(server.params, {"tokens": prompts})
    out = [logits]
    for i in range(STEPS - 1):
        toks = jnp.argmax(out[-1], -1)
        logits, cache = server.decode(server.params, cache,
                                      server.step_batch(toks),
                                      jnp.asarray(PROMPT + i, jnp.int32))
        out.append(logits)
    logits = jnp.stack(out, 1)
    copies = int(sum(jnp.sum(c) for c in drv.routed(cache)))
    jax.block_until_ready(logits)
    return np.asarray(prompts), np.asarray(jnp.argmax(logits, -1)), \
        np.asarray(logits, np.float32), copies


def test_float32_program_matches_the_reference():
    """Prefill then decode through the latent cache give the reference's
    logits at every served position. Both sides compute in float32, so the
    tolerance covers only the order of operations: the absorbed decode
    against the reference's per-head keys, and blockwise against whole
    softmaxes (2e-4 of logits of about 1)."""
    cfg = small()
    prompts, toks, got, copies = served(cfg)
    want = drv.reference(cfg, SEED, prompts, toks)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4, rtol=2e-4)
    # each decode step routes B tokens 4 times; the 4 held of 16 experts
    # see some of those copies, never more than all
    assert 0 < copies <= (STEPS - 1) * B * 4


def test_bfloat16_serving_lies_within_rounding_and_float8_does_not():
    """Served in bfloat16 the program's tokens lie within rounding of the
    reference's best; the float8 control strays further from the
    reference, at the same positions, than the program does."""
    cfg = small("bfloat16")
    prompts, toks, got, _ = served(cfg)
    ref = np.asarray(drv.reference(cfg, SEED, prompts, toks))
    f8 = np.asarray(drv.reference(cfg, SEED, prompts, toks, fp8=True))
    program = np.abs(got - ref).max()
    assert program < 0.05, program
    assert np.abs(f8 - ref).max() > 2 * program
    assert check.gap(ref, toks) <= 2 * program


def test_gaps_are_the_widest_as_check_reads_it_and_carry_the_mean():
    ref = jnp.asarray([[[1.0, 0.5, 0.25], [0.0, 2.0, 1.0]]])
    picked = np.asarray([[1, 2]])
    gaps = drv.Gaps.of(ref, picked)
    assert float(gaps) == check.gap(ref, picked) == 1.0
    assert gaps.mean == 0.75


def test_reference_refuses_what_it_does_not_compute():
    with pytest.raises(NotImplementedError):
        mla_moe.check_supported(small(scoring_func="softmax"))
    with pytest.raises(NotImplementedError):
        mla_moe.check_supported(small(rope_scaling=None))


def test_weights_are_the_same_made_alone_or_stacked():
    cfg = small()
    st = W.stacked(cfg, SEED, jnp.bfloat16)
    one = W.layer(cfg, SEED, 2)
    for name in ("wq_b", "ewg", "router", "kv_a_norm"):
        np.testing.assert_array_equal(
            np.asarray(st["moe"][name][1], np.float32),
            np.asarray(one[name].astype(st["moe"][name].dtype), np.float32))
    assert st["moe"]["router"].dtype == jnp.float32


def test_levelled_bias_evens_the_experts_loads():
    """Scores with a common offset per expert, as random weights give:
    picked by score alone, a few experts take most copies; with the
    levelled bias every expert's load lies near the mean."""
    rng = np.random.default_rng(0)
    scores = jax.nn.sigmoid(jnp.asarray(
        rng.normal(0, 1.0, (16,)) + rng.normal(0, 0.5, (4096, 16)),
        jnp.float32))
    kw = dict(n_group=4, topk_group=2, top_k=4)

    def loads(bias):
        return np.bincount(np.asarray(mla_moe.pick(scores + bias, **kw))
                           .ravel(), minlength=16)
    mean = 4096 * 4 / 16
    assert loads(0.0).max() > 1.5 * mean
    assert np.abs(loads(mla_moe.level(scores, **kw)) - mean).max() < \
        0.05 * mean


def test_attention_scores_spread_about_two_at_published_widths():
    """One head of the seed's MLA at the configuration's widths: its scores
    (nope and rope parts, times the softmax scale) over random positions
    spread about 2, so a head attends to tens of positions, not to the
    average of the whole document."""
    with open(os.path.join(harness.BENCH, "configs",
                           "deepseek-v3.json")) as f:
        cfg = json.load(f)
    n = W.dims(cfg)
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    w = {name: W.make(k, name, shape, jnp.float32) for k, (name, shape) in
         zip(keys, {"wq_a": (n["d"], n["ql"]),
                    "wq_b": (n["ql"], n["dn"] + n["dr"]),
                    "wkv_a": (n["d"], n["r"]), "wk_nope": (n["r"], n["dn"]),
                    "wk_rope": (n["d"], n["dr"])}.items())}
    eps = float(cfg["rms_norm_eps"])
    ones = {k: jnp.ones((v,)) for k, v in (("d", n["d"]), ("ql", n["ql"]),
                                           ("r", n["r"]))}
    h = mla_moe._rms_norm(jax.random.normal(keys[5], (256, n["d"])),
                          ones["d"], eps)
    q = mla_moe._rms_norm(h @ w["wq_a"], ones["ql"], eps) @ w["wq_b"]
    k = jnp.concatenate([mla_moe._rms_norm(h @ w["wkv_a"], ones["r"], eps)
                         @ w["wk_nope"], h @ w["wk_rope"]], -1)
    s = np.asarray(q @ k.T) * mla_moe.softmax_scale(cfg)
    spread = s[~np.eye(len(s), dtype=bool)].std()
    assert 1.6 < spread < 2.8, spread


DIMS = {"d": 8, "H": 2, "ql": 4, "r": 4, "dn": 2, "dr": 2, "dv": 3, "F": 6,
        "f": 5, "ns": 1, "E": 16, "El": 4, "K": 2, "V": 10, "L": 3, "Ld": 1}


def test_decode_flops_by_hand():
    # MLA: wq_a 32 + wq_b 4*2*4=32 + wkv_a 32 + wk_rope 16 + absorb 2*2*4=16
    # + out of latent 2*4*3=24 + wo 2*3*8=48 = 200 a layer
    assert flops_mla_moe.mla_decode_params(DIMS) == 200
    # one row at 5 live: 2*(3*200 + 1*3*8*6 + 2*(3*8*5 + 8*16) + 8*10)
    #   = 2*(600 + 144 + 496 + 80) = 2640; attention 3 * 2*1*2*5*(8+2) = 600;
    # 7 copies * 2*3*8*5 = 1680
    assert flops_mla_moe.decode_step_flops(DIMS, 1, 5, 7) == 2640 + 600 + 1680
    f, b = flops_mla_moe.mla_decode_cost(2, 5, 2, 4, 2)
    assert f == 2 * 2 * 2 * 5 * 10
    assert b == (2 * 5 * 6 + 2 * 2 * 6 + 2 * 2 * 4) * 2


PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


class Op:
    def __init__(self, name, start, end, text, run):
        self.name, self.start, self.end = name, start, end
        self.text, self.run = text, run


class Trace:
    """Two decode-step runs and a prefill run in the window, as
    ``tracefile.Trace`` holds them."""
    window = (0, 10_000_000)
    runs = {"/device:TPU:0": [(100, 2_000_000, "jit_decode_step(1)"),
                              (3_000_000, 5_000_000, "jit_prefill_step(2)"),
                              (6_000_000, 8_000_000, "jit_decode_step(1)")]}

    def __init__(self, per_run=None):
        self.per_run = per_run
        self.device_ops = {"/device:TPU:0": [
            # the first decode run: the layer loop, two expert loops in it
            Op("while.34", 200, 1_800_000, "", 0),
            Op("while.35", 300_000, 600_000, "", 0),
            Op("fusion.2", 400_000, 500_000, "", 0),
            Op("while.35", 900_000, 1_400_000, "", 0),
            Op("mla_decode_attention.3", 1_500_000, 1_600_000, "", 0),
            # the prefill's loops are not read
            Op("while.7", 3_100_000, 4_600_000, "", 1),
            Op("while.8", 3_200_000, 3_600_000, "", 1),
            # the second decode run
            Op("while.34", 6_100_000, 7_000_000, "", 2),
            Op("while.35", 6_200_000, 6_500_000, "", 2)]}

    def kernel_per_run(self, kernel, program):
        return self.per_run if kernel == "mla_decode_attention" else []


def decode_run(trace, copies=None):
    return harness.Run(
        e2e={}, attempted=0, failed=0, checks={},
        steps=[{"kind": "decode", "t0": 0.0, "t1": 0.5, "B": 4,
                "live": 100 + i} for i in range(2)],
        info={"dims": DIMS, "copies": copies}, trace=trace, peak=PEAK)


def test_expert_ms_reads_the_loops_nested_in_decode_runs():
    reader = harness.Files().metric("moe.expert_ms_per_decode")
    # (0.3 + 0.5) ms in the first run, 0.3 in the second; the layer loops,
    # the ops inside the expert loops and the prefill's loops are not
    # counted
    assert reader.read(decode_run(Trace())) == pytest.approx(1.1 / 2)
    assert reader.read(decode_run(None)) is None


def test_mla_decode_roofline_is_least_time_over_kernel_time():
    reader = harness.Files().metric("mla_decode_roofline")
    least = sum(flops.least_seconds(*flops_mla_moe.mla_decode_cost(
        4, 100 + i, 2, 4, 2), PEAK) for i in range(2)) * DIMS["L"]
    assert reader.read(decode_run(Trace([(3, 1e-3)] * 2))) == pytest.approx(
        100 * least / 2e-3)
    assert reader.read(decode_run(Trace([(2, 1e-3)] * 2))) is None


def test_decode_mfu_counts_the_routed_copies_once():
    reader = harness.Files().metric("mla_moe.decode_mfu")
    base = sum(flops_mla_moe.decode_step_flops(DIMS, 4, 100 + i, 0)
               for i in range(2))
    got = reader.read(decode_run(None, copies=11))
    assert got == pytest.approx(100 * (base + 11 * 2 * 3 * 8 * 5) / 1.0
                                / PEAK["bf16_flops_per_s"])
    assert reader.read(decode_run(None)) is None


def test_held_shares_add_up_to_the_uncut_reference_layer():
    """Four chips' shares of a 16-expert layer (4 held each), with the
    shared expert that every chip computes counted once, add up to the
    reference's uncut layer: every routed copy lands on exactly one share.
    Both sides in float32; the tolerance covers the order of the sums."""
    from repro.models import layers as L
    from repro.parallel.sharding import ParallelConfig, ShardCtx
    uncut = small(n_routed_experts=16, first_expert_held=0)
    w = W.layer(uncut, SEED, 2)
    bias = mla_moe.router_bias(uncut, SEED)[1]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64)) * 3.0
    want = mla_moe._moe(x, w, bias, eps=1e-6, first_held=0, fp8=False,
                        route_kw=dict(n_group=4, topk_group=2, top_k=4,
                                      scaling=2.5)) - x
    h = mla_moe._rms_norm(x, w["ln2"], 1e-6)
    px = ShardCtx(mesh=None, pcfg=ParallelConfig())
    total, shared = 0.0, None
    for first in (0, 4, 8, 12):
        arch = drv.arch_config("small", small(first_expert_held=first))
        p = {"router": w["router"], "router_bias": jnp.asarray(bias),
             "wg": w["ewg"][first:first + 4], "wu": w["ewu"][first:first + 4],
             "wd": w["ewd"][first:first + 4],
             "shared": {"wg": w["swg"], "wu": w["swu"], "wd": w["swd"]}}
        y, _ = L.moe_held(p, h, cfg=arch, px=px)
        total = total + y
        shared = L.mlp(p["shared"], h, arch, px)
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(want), rtol=1e-4, atol=1e-5)

"""Readings that a serve cell's limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13

For each seed, in one process: the seed's weights in the cell's server,
enough whole rounds of the cell's traffic through the timed calls to fill
the sample that a run checks, and that sample. Then the same rounds with
the timed path broken underneath, once for each fault a serve cell can
have (``FAULTS``). Prints, per seed, the served tokens' widest gap below
the reference's best logit for the program, for the float8 control (the
tokens it ranks first at the same positions) and for each fault, each
judged against the cell's limit by the harness's own rule. The benchmark's
own runs never run the control or a fault.
"""
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def stale_cache(server) -> None:
    """Every decode step returns its cache unchanged."""
    decode = server.decode
    server.decode = lambda p, c, b, pos: (decode(p, c, b, pos)[0], c)


def altered_token(server) -> None:
    """Every decoded token is altered where it is produced."""
    step, vocab = server.decode_step, server.cfg.vocab_size

    def altered():
        dt = step()
        server.toks = (server.toks + 1) % vocab
        return dt
    server.decode_step = altered


FAULTS = {"stale_cache": stale_cache, "altered_token": altered_token}


def judged(value: float, limit: float) -> bool:
    """``correct`` as a run with this one reading would print it."""
    from bench.harness import Run
    return Run(e2e={}, attempted=0, failed=0, checks={
        "served_logit_gap": {"value": value, "limit": limit}}).correct


def readings(ctx, seeds, faults=tuple(FAULTS), out=print) -> list:
    """For each seed: {"seed", "program", "control", <fault>...}, each a
    widest gap."""
    import gc
    from bench import check
    serve = ctx.files.driver(ctx.workload["driver"])
    chk = ctx.workload["check"]
    rows = chk.get("rows", 1)
    server = None
    table = []
    for seed in seeds:
        t0 = time.perf_counter()
        if server is None:
            server, traffic = serve.build_server(ctx, seed)
        else:
            server.params = serve.program_params(ctx.config, seed,
                                                 server.cfg)
            traffic = serve.ClosedLoop(ctx.traffic, ctx.config["vocab_size"],
                                       seed)
        n_rounds = -(-chk["requests"] // traffic.clients)

        def sample():
            rounds = [serve.serve_round(ctx, server, traffic, i, [])
                      for i in range(n_rounds)]
            server.cache = None
            return serve.sample(rounds, traffic, seed, chk["requests"])

        prompts, served = sample()
        broken = {}
        for name in faults:
            FAULTS[name](server)
            broken[name] = sample()[1]
            for attr in ("decode", "decode_step"):
                server.__dict__.pop(attr, None)
            server._derive()
        server.params = None
        gc.collect()
        ref = check.reference(ctx.config, seed, prompts, served, rows)
        row = {"seed": seed, "program": check.gap(ref, served),
               "control": check.control_gap(ctx.config, seed, prompts,
                                            served, rows, ref=ref)}
        del ref
        for name, toks in broken.items():
            row[name] = check.served_gap(ctx.config, seed, prompts, toks,
                                         rows)
        table.append(row)
        limit = chk["served_logit_gap"]
        out(f"seed {seed} ({served.size} served tokens, "
            f"{time.perf_counter() - t0:.1f} s): " + ", ".join(
                f"{k} {v!r} (correct {judged(v, limit)})"
                for k, v in row.items() if k != "seed"))
    return table


def main(argv=None) -> int:
    import argparse
    import json
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    harness.require_chips(1)
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      harness.compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctx = harness.Ctx(harness.Files(), args.workload, 0, 0.0, False,
                      time.perf_counter())
    seeds = [int(s) for s in args.seeds.split(",")]
    table = readings(ctx, seeds, out=lambda s: print(s, flush=True))
    limit = ctx.workload["check"]["served_logit_gap"]
    print(json.dumps({"workload": args.workload, "limit": limit,
                      "readings": table}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

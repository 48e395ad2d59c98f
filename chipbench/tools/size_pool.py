#!/usr/bin/env python3
"""Size a serving cell's rows and page pool from ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 chipbench/tools/size_pool.py \
        --config chipbench/configs/granite-3.0-2b.json \
        --traffic chipbench/traffic/chat-poisson.json --rows 10 11 12

Compiles, for one described TPU v5e chip (no chip needed), the three
programs that hold memory while a serving cell runs: the paged decode
step at ``rows`` rows over a pool of ``pages`` pages, the prefill of the
largest prompt in the largest admitted group, and the scatter of that
prefill into the pool. For each row count the pool holds every row at
its full slot length, so that no request is ever evicted; the weights
stay resident throughout. It prints one JSON line per row count: the
bytes each program plans (arguments, outputs, temporaries), the
fullest moment (weights + pool + the largest of decode's temporaries,
and prefill's output plus the larger of prefill's and the scatter's
temporaries) and whether that fits the chip's memory less ``--reserve``.
The row count of the traffic file is the largest that fits.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

_HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(_HERE.parents[1]))
sys.path.insert(0, str(_HERE.parents[1] / "src"))

CHIP_BYTES = int(15.75 * 1024 ** 3)  # the HBM the v5e compiler plans with


def _ma(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"args": int(m.argument_size_in_bytes),
            "out": int(m.output_size_in_bytes),
            "alias": int(m.alias_size_in_bytes),
            "temp": int(m.temp_size_in_bytes)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rows", type=int, nargs="+", required=True)
    ap.add_argument("--reserve", type=float, default=0.5 * 1024 ** 3,
                    help="bytes kept free for the runtime and small arrays")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from chipbench import harness
    from chipbench.drivers import serve
    from repro.models import build_model
    from repro.runtime.paging import PagePool

    config = harness.load_json(args.config)
    traffic = harness.load_json(args.traffic)
    page = traffic["engine"]["page_size"]
    plen = max(traffic["prompt"]["menu"])
    group = max(g for g in (16, 4, 1)
                if g <= traffic["engine"]["max_admits_per_step"])
    slot_len = plen + traffic["output"]["max"]
    pages_per_slot = -(-slot_len // page)
    mcfg = serve.model_config(config, slot_len)
    model = build_model(mcfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    weights = sum(s.size * s.dtype.itemsize
                  for s in jax.tree_util.tree_leaves(params))

    def step(p, cache, tokens, pos, tables):
        logits, new_cache = model.decode_step_paged(p, cache, tokens, pos,
                                                    tables)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), \
            new_cache

    prefill = jax.jit(model.prefill, static_argnames=("cache_len",))
    toks = jax.ShapeDtypeStruct((group, plen), jnp.int32, sharding=chip)
    pre = _ma(prefill.lower(params, {"tokens": toks},
                            cache_len=-(-plen // page) * page).compile())
    _, pre_cache, _ = jax.eval_shape(
        lambda p, t: model.prefill(p, {"tokens": t},
                                   cache_len=-(-plen // page) * page),
        params, toks)
    pre_cache = on_chip(pre_cache)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,  # noqa: E731
                                              sharding=chip)
    for rows in args.rows:
        pages = rows * pages_per_slot
        cache = on_chip(jax.eval_shape(
            lambda: model.init_cache(pages + 1, page, None)))
        pool = sum(s.size * s.dtype.itemsize
                   for s in jax.tree_util.tree_leaves(cache))
        dec = _ma(jax.jit(step, donate_argnums=(1,)).lower(
            params, cache, i32(rows, 1), i32(rows),
            i32(rows, pages_per_slot)).compile())
        shim = type("Shim", (), {"page_size": page})()
        scatter = jax.jit(
            lambda b, s, ids, r: PagePool._scatter_impl(
                shim, b, s, ids, r, n_pages=-(-plen // page)),
            donate_argnums=(0,))
        sca = _ma(scatter.lower(cache, pre_cache, i32(-(-plen // page)),
                                i32()).compile())
        pre_out = sum(s.size * s.dtype.itemsize
                      for s in jax.tree_util.tree_leaves(pre_cache))
        fullest = weights + pool + max(dec["temp"],
                                       pre_out + max(pre["temp"],
                                                     sca["temp"]))
        print(json.dumps({
            "rows": rows, "slot_len": slot_len, "pages": pages,
            "weights": weights, "pool": pool, "decode": dec,
            "prefill": dict(pre, group=group, prompt=plen),
            "scatter": sca, "fullest": fullest,
            "fits": fullest <= CHIP_BYTES - args.reserve}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

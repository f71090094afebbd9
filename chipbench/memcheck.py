"""Does a configuration's pool leave room for its largest programs?

    JAX_PLATFORMS=cpu python3 chipbench/memcheck.py <config> [--all]

Compiles the engine's prefill and decode-slice programs for a described
TPU v5e (no chip needed) at the configuration's widths and pool, for the
largest prefill and decode shape of every rows bucket the scheduler can
reach (``grid``), or for every reachable shape with ``--all``, and prints
each program's temporaries next to the weights and the pool.  A program
that does not fit is refused here by the TPU compiler, as it would be on
the chip.  The engine's own jitted functions are used, built around a
pool of one page on the host; only their shapes are given the real
sizes.  The pool is the arrays that engine holds, each with its page
axis at the configuration's ``kv_pool_pages`` (plus the null page): their
number, record width and type are the engine's own.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
_HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(_HERE.parent), str(_HERE.parent / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import grid, spec  # noqa: E402
from chipbench.harness import model_config  # noqa: E402

GiB = 2 ** 30


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("config")
    ap.add_argument("--all", action="store_true")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from repro.engine.static_engine import StaticEngine
    from repro.models.registry import get_model

    jax.config.update("jax_enable_compilation_cache", False)
    conf = spec.config(args.config)
    sv = conf["serving"]
    cfg = model_config(conf)
    model = get_model(cfg)
    pg, S = sv["page_tokens"], sv["slice_len"]
    eng = StaticEngine(model, None, len_bucket=sv["len_bucket"],
                       kv_layout="paged", page_tokens=pg, kv_pool_tokens=pg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pools = [sds(x.shape[:1] + (sv["kv_pool_pages"] + 1,) + x.shape[2:],
                 x.dtype) for x in (eng._k_pages, eng._v_pages)]
    w_bytes = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    p_bytes = sum(x.size * x.dtype.itemsize for x in pools)
    print(f"{args.config}: weights {w_bytes / GiB:.3f} GiB, pool "
          f"{p_bytes / GiB:.3f} GiB ({sv['kv_pool_pages']} pages; "
          f"{len(pools)} arrays of {pools[0].shape} {pools[0].dtype})")
    max_len = conf["max_effective_input"]
    pre = grid.prefill_shapes(sv, max_len)
    dec = grid.decode_shapes(sv, max_len)
    if not args.all:  # the largest of each rows bucket
        pre = [max((s for s in pre if s[0] == b), key=lambda s: s[1])
               for b in sorted({s[0] for s in pre})]
        dec = [max((s for s in dec if s[0] == b), key=lambda s: s[1])
               for b in sorted({s[0] for s in dec})]
    i32 = jnp.int32
    worst = 0.0
    for kind, shapes in (("prefill", pre), ("decode", dec)):
        for b, x in shapes:
            t0 = time.time()
            try:
                if kind == "prefill":
                    fn = eng._prefill_paged
                    low = fn.lower(params, sds((b, x), i32), sds((b,), i32),
                                   *pools,
                                   sds((b, grid.ceil_div(x, pg)), i32))
                else:
                    fn = eng._get_compiled_paged(S)
                    low = fn.lower(params, *pools, sds((b, x), i32),
                                   sds((b, x * pg), i32), sds((b,), i32),
                                   sds((b,), i32), sds((b,), i32))
                m = low.compile().memory_analysis()
                temp = m.temp_size_in_bytes / GiB
                worst = max(worst, temp)
                total = (w_bytes + p_bytes) / GiB + temp
                print(f"  {kind} rows {b} x {x}: temp {temp:.3f} GiB, "
                      f"total {total:.3f} GiB ({time.time() - t0:.1f} s)",
                      flush=True)
            except Exception as e:  # the compiler's refusal is the finding
                print(f"  {kind} rows {b} x {x}: REFUSED "
                      f"{str(e).splitlines()[0][:200]}", flush=True)
                worst = float("inf")
    print(f"largest temporaries {worst:.3f} GiB; weights + pool + that = "
          f"{(w_bytes + p_bytes) / GiB + worst:.3f} GiB")


if __name__ == "__main__":
    main()

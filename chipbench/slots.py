"""How many cache slots a configuration's chip can hold: the serving
programs compiled for a described TPU v5e, with nothing run.

    JAX_PLATFORMS=cpu python3 chipbench/slots.py --config danube3-4b \
        --slots 10 11 16

For each slot count it compiles, at the configuration's published widths
and full depth, the runner's decode step over every slot, the prefill of
the longest prompt a mix can send, and the insert of its cache into a slot,
and prints each program's ``memory_analysis``. The weights and the cache
stay resident while prefill and insert run, so the bytes a slot count needs
are the weights plus the cache plus the largest program's own arguments,
outputs and temporaries beyond them. A count is taken when it leaves at
least ``--headroom`` bytes of the chip's memory.

It builds the runner's jitted functions without allocating its cache:
``JaxRunner.__new__`` and the attributes those functions read. Run it on a
host without a TPU attached; nothing is placed on a device.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_LIMIT = 16_909_336_064        # bytes_limit of one v5e chip, as JAX reports


def programs(cfg_file, slots, prompt, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from chipbench.run import model_config
    from repro.core.runner import JaxRunner
    from repro.models import transformer as T
    from repro.parallel.sharding import ParallelContext, make_mesh
    cfg = model_config(cfg_file)
    mesh = cfg_file["serve"]["mesh"]
    max_len = cfg_file["serve"]["max_len"]
    n = mesh[0] * mesh[1]
    if n == 1:
        ctx = ParallelContext(mesh=None)
        one = SingleDeviceSharding(topo.devices[0])
        place = lambda tree: jax.tree_util.tree_map(   # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)
        p_sh = s_sh = None
        rep = one
    else:
        m = make_mesh(tuple(mesh), ("data", "model"), devices=topo.devices[:n])
        ctx = ParallelContext(mesh=m)
        p_sh = T.param_shardings(cfg, ctx, "serve")
        s_sh = T.decode_state_shardings(cfg, ctx)
        rep = NamedSharding(m, P())
        place = None
    params = jax.eval_shape(lambda: T.init_params(
        cfg, jax.random.PRNGKey(0), ParallelContext(mesh=None), "serve",
        jnp.bfloat16))
    state = jax.eval_shape(lambda: T.init_decode_state(
        cfg, ctx, slots, max_len, jnp.bfloat16))
    if place is not None:
        params, state = place(params), place(state)
    else:
        params = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            params, p_sh)
        state = jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            state, s_sh)
    r = JaxRunner.__new__(JaxRunner)
    r.cfg, r.ctx, r.max_slots, r.max_len = cfg, ctx, slots, max_len
    r._jnp, r._T = jnp, T
    r._slot_axes = T.decode_slot_axes(cfg)
    r._pin = (lambda st: st) if s_sh is None else (
        lambda st: jax.lax.with_sharding_constraint(st, s_sh))
    vec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=rep)
    decode = jax.jit(r._masked_decode, donate_argnums=(1,)).lower(
        params, state, vec((slots, 1), jnp.int32), vec((slots,), bool))
    prefill = jax.jit(lambda p, tok: T.prefill(
        p, tok, cfg, ctx, max_len=max_len, cache_dtype=jnp.bfloat16)).lower(
        params, vec((1, prompt), jnp.int32))
    fresh = jax.eval_shape(lambda p, t: T.prefill(
        p, t, cfg, ctx, max_len=max_len, cache_dtype=jnp.bfloat16)[1],
        params, jax.ShapeDtypeStruct((1, prompt), jnp.int32))
    fresh = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        fresh, s_sh if s_sh is not None
        else jax.tree_util.tree_map(lambda _: one, fresh))
    insert = jax.jit(r._insert, donate_argnums=(0,)).lower(
        state, fresh, vec((), jnp.int32))
    return {"decode": decode, "prefill": prefill, "insert": insert}, \
        params, state


def nbytes(tree, per_device: int):
    import jax
    return sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(
        tree)) / per_device


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--slots", type=int, nargs="+", required=True)
    ap.add_argument("--prompt", type=int, default=3584,
                    help="the longest prompt the cell's mixes send")
    ap.add_argument("--headroom", type=float, default=0.9e9)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    import jax
    from jax.experimental import topologies
    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "chipbench", "configs",
                           args.config + ".json")) as f:
        cfg_file = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chips = cfg_file["serve"]["mesh"][0] * cfg_file["serve"]["mesh"][1]
    best = None
    for slots in args.slots:
        lowered, params, state = programs(cfg_file, slots, args.prompt, topo)
        row = {"slots": slots, "weights_bytes": nbytes(params, chips),
               "cache_bytes": nbytes(state, chips)}
        need = 0.0
        for name, low in lowered.items():
            try:
                mem = low.compile().memory_analysis()
            except Exception as e:            # the compiler refuses it
                row[name] = f"refused: {str(e).splitlines()[0][:200]}"
                need = float("inf")
                continue
            own = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                   + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
            row[name] = {"arguments": mem.argument_size_in_bytes,
                         "outputs": mem.output_size_in_bytes,
                         "temp": mem.temp_size_in_bytes,
                         "alias": mem.alias_size_in_bytes}
            # prefill does not take the cache, which stays resident beside it
            extra = own if name != "prefill" else own + nbytes(state, chips)
            need = max(need, extra)
        row["left_bytes"] = HBM_LIMIT - need
        print(json.dumps(row), flush=True)
        if row["left_bytes"] >= args.headroom:
            best = slots
    print(json.dumps({"config": args.config, "largest_slots": best,
                      "headroom": args.headroom, "hbm_limit": HBM_LIMIT}))


if __name__ == "__main__":
    main()

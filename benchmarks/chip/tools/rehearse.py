#!/usr/bin/env python3
"""Compile each cell's programs for a described TPU v5e (``v5e:2x2``) with
no chip attached, and print their memory analysis and Mosaic kernels.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tools/rehearse.py [cell ...]

What the chip's compiler refuses, or what does not fit its memory, shows
here at no chip time.  Nothing runs, so this gives no time.
"""
import collections
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def mosaic(compiled) -> dict:
    names = collections.Counter()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = re.search(r'jit\((\w+)\)/pallas_call', line)
            names[m.group(1) if m else "unnamed"] += 1
    return dict(names)


def memory(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: getattr(m, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes",
        "generated_code_size_in_bytes")}


def train(cell, topo):
    import jax
    import jax.numpy as jnp
    from bench.program import dtype_of, program_config
    from repro.dist.steps import make_train_step, node_stack_specs
    from repro.kernels.ops import KernelConfig
    from repro.launch.mesh import make_mesh
    from repro.models import model as M

    mix = cell.mix
    cfg = program_config(cell.model, cell.arch)
    shape = tuple(mix["mesh"])
    n_dev = shape[0] * shape[1]
    mesh = make_mesh(shape, ("data", "model"), devices=topo.devices[:n_dev])
    dt = dtype_of(cell.model)
    b = make_train_step(cfg, mesh, topology=mix["topology"], k=mix["k"],
                        method_name=mix["method"], eta=mix["eta"],
                        momentum=mix["momentum"], param_dtype=dt,
                        remat=mix["remat"],
                        kernel_config=KernelConfig(backend="pallas"))
    n = b.n_nodes
    p = node_stack_specs(M.param_specs(cfg, dt), n)
    o = jax.eval_shape(b.method.init, p)
    bs = {k: jax.ShapeDtypeStruct((n, mix["rows_per_node"], mix["seq"]),
                                  jnp.int32) for k in ("tokens", "labels")}
    c = b.step_fn.lower(p, o, bs, jax.ShapeDtypeStruct((), jnp.int32)).compile()
    hlo = c.as_text()
    return {"step": {**memory(c), "mosaic": mosaic(c),
                     "collective_permutes": hlo.count("collective-permute-start")
                     or hlo.count("collective-permute("),
                     "all_reduces": hlo.count("all-reduce-start")
                     or hlo.count("all-reduce(")}}


def serve(cell, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from bench.program import dtype_of, program_config
    from repro.kernels.ops import KernelConfig
    from repro.models import model as M
    from repro.models.model import PagedCacheLayout
    from repro.serve import ContinuousEngine

    mix = cell.mix
    cfg = program_config(cell.model, cell.arch)
    dt = dtype_of(cell.model)
    one = SingleDeviceSharding(topo.devices[0])
    ps = mix["page_size"]
    layout = PagedCacheLayout(page_size=ps, num_pages=mix["num_pages"],
                              max_pages_per_slot=mix["pages_per_slot"])
    # the engine allocates its pools on construction: build it around
    # shapes only
    pools = jax.eval_shape(lambda: M.init_paged_cache(cfg, layout, dt))
    eng = ContinuousEngine.__new__(ContinuousEngine)
    eng.__dict__.update(
        cfg=cfg, layout=layout, max_new=mix["max_new"],
        kcfg=KernelConfig(backend="pallas"), sampling=__import__(
            "repro.serve.sampling", fromlist=["SamplingParams"]
        ).SamplingParams(), cache_dtype=dt, speculate_k=0,
        _prefill_fns={}, _decode_fn=None, dispatch_counter={})
    sds = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), t)
    p, pl = sds(M.param_specs(cfg, dt)), sds(pools)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa
    u32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.uint32, sharding=one)  # noqa
    out = {}
    for bl in mix["buckets"]:
        c = eng._get_prefill(bl, 1).lower(p, pl, i32(1, bl), i32(1),
                                          i32(1, bl // ps), u32(1, 2)).compile()
        out[f"prefill_{bl}"] = {**memory(c), "mosaic": mosaic(c)}
    b, maxp = mix["slots"], mix["pages_per_slot"]
    c = eng._get_decode().lower(p, pl, i32(b, maxp), i32(b), i32(b),
                                u32(b, 2)).compile()
    out["decode"] = {**memory(c), "mosaic": mosaic(c)}
    return out


def main(argv):
    import jax
    from jax.experimental import topologies
    from bench import spec
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    names = argv or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = spec.Cell(name)
        fn = train if cell.mix["kind"] == "train" else serve
        print(json.dumps({"workload": name, "programs": fn(cell, topo)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

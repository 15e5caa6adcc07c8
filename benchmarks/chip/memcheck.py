#!/usr/bin/env python3
"""Compile a configuration's served programs for a described TPU v5e chip,
without the chip, and print what each needs in device memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/memcheck.py \
        benchmarks/chip/configs/<name>.json [slots]

The engine is built abstractly through the configuration's plug-in
(``jax.eval_shape``: no weights or pages are allocated), then its paged
decode launch and one prefill chunk are lowered with shapes placed on one
device of a ``v5e:2x2`` topology and compiled by the TPU compiler, the
paged-attention kernel as Mosaic compiles it for the chip (the host is a
CPU, where the kernel would otherwise be interpreted).  Weights, the page
pools and each program's temporaries together must fit one chip at the
configuration's slot count.
"""
from __future__ import annotations

import json
import os
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402
from repro.kernels.paged_attention import ops as paged_ops  # noqa: E402

import run  # noqa: E402

GiB = 2 ** 30


def main(path: str, slots: int = 0) -> None:
    cfg = json.load(open(path))
    e = cfg["engine"]
    if slots:
        e["slots"] = slots
    arch = run.load_arch(cfg)
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    box = {}

    def build():
        eng, w = run.build_engine(cfg, arch, 0, run.SpanLog())
        box["kv"] = eng.kv
        return w, eng.kv.k_pool, eng.kv.v_pool

    w_shape, k_pool, v_pool = jax.eval_shape(build)
    kv = box["kv"]

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            tree)

    B, nb = int(e["slots"]), kv.n_blk
    i32 = jnp.int32
    args = on_chip((w_shape, k_pool, v_pool,
                    jax.ShapeDtypeStruct((B, nb), i32),
                    jax.ShapeDtypeStruct((B,), i32),
                    jax.ShapeDtypeStruct((B, 1, 1), i32)))
    w_b = sum(x.size * x.dtype.itemsize
              for x in jax.tree_util.tree_leaves(w_shape))
    p_b = sum(p.size * p.dtype.itemsize for p in (k_pool, v_pool))
    print(f"{cfg['name']}: slots {B}, max_seq {e['max_seq']}, "
          f"weights {w_b / GiB:.2f} GiB, page pools {p_b / GiB:.2f} GiB")
    # the launches themselves: a jit around one would drop its donation
    decode = kv._decode_slots.__wrapped__
    extend = kv._extend.__wrapped__
    chunk = int(e["prefill_chunk"])
    ext = on_chip((w_shape, k_pool, v_pool, jax.ShapeDtypeStruct((nb,), i32),
                   jax.ShapeDtypeStruct((), i32),
                   jax.ShapeDtypeStruct((1, chunk), i32)))
    with mock.patch.object(paged_ops, "interpret_mode", lambda: False):
        m_dec = decode.lower(*args).compile().memory_analysis()
        m_ext = extend.lower(*ext).compile().memory_analysis()
    for name, m in (("decode_paged (Mosaic kernel)", m_dec),
                    (f"extend_paged ({chunk} tokens)", m_ext)):
        print(f"{name} compiles: args "
              f"{m.argument_size_in_bytes / GiB:.2f} GiB, out "
              f"{m.output_size_in_bytes / GiB:.2f} GiB, temp "
              f"{m.temp_size_in_bytes / GiB:.2f} GiB, aliased "
              f"{m.alias_size_in_bytes / GiB:.2f} GiB")
    print("temp is the program's buffers summed, not their peak; the "
          "compiler refuses a program whose peak exceeds the chip")


if __name__ == "__main__":
    main(sys.argv[1], *(int(a) for a in sys.argv[2:3]))

"""sha256 of every served model's lowered programs at tiny sizes, for a
described TPU (the Mosaic kernels in) and for the CPU's reference backend:
the whole StableHLO, the StableHLO outside the kernels' serialized bodies
(those carry source lines, so they differ whenever a line of a caller
moves), and the jaxpr, which has the kernels' bodies and no source lines.

    cd <tree> && JAX_PLATFORMS=cpu python3 devbench/lowered_programs.py [model ...]

To compare two trees lay them at the same path in turn (a kernel's body
names its files) and diff the two outputs: a PR that touches code the
models share shows with it that their programs are what they were. The
models are those of ``llm/config.SERVING_MODULES``, each at its ``tiny``
configuration; a tree from before PR 44 has no such table, and there
``MODULES`` below names where each model's programs lived (PR 38 to 43).
DUMP=<dir> also writes the texts; BURST=<n> lowers ``decode_burst`` at n
steps and not at 4. With models named (``llama``, ``lfm2``,
...) only theirs are lowered: a kernel's body is traced once a process and
shape and keeps the source lines of the caller that traced it, so in one
process a model whose file moved hands its new lines to every later model
that runs the same kernel at the same shapes; a model a process (or all
but the edited one in one) tells the two apart. Imported
(tests/test_tpu_aot.py holds three models' jaxpr hashes), nothing runs:
``run(backend, names)`` gives the hashes of the models named."""
import dataclasses
import hashlib
import importlib
import json
import os
import re
import sys

sys.path.insert(0, os.getcwd())
os.environ.setdefault("TPU_LOG_DIR", "disabled")
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import NamedSharding, PartitionSpec as P
from ray_tpu.llm import config as llm_config
from ray_tpu.ops.kernels import force_kernel_backend
from ray_tpu.parallel.mesh import MeshSpec, build_mesh

SLOTS, MAX_SEQ, CHUNK = 4, 256, 32
# Steps of the burst lowered; BURST=1 lowers the program of a lone step (SDAR's burst of one block).
BURST = int(os.environ.get("BURST", 4))
# What a model's ``tiny`` takes beside the length and the dtype.
TINY = {"LlamaConfig": lambda c: dataclasses.replace(c.tiny(), vocab_size=512, dtype="bfloat16"),
        "LongcatConfig": lambda c: c.tiny(expert_shards=2, max_seq_len=MAX_SEQ, dtype="bfloat16"),
        # a share of the experts, both kinds of layer, the sink: the decode kernel's program with its extra operand
        "MimoConfig": lambda c: c.tiny(expert_shards=2, max_seq_len=MAX_SEQ, dtype="bfloat16"),
        # a share of the experts, both kinds of mixer in a period that repeats
        "GraniteConfig": lambda c: c.tiny(expert_shards=2, max_seq_len=MAX_SEQ, dtype="bfloat16"),
        # a share of the experts, and more positions in a line than the 8 a query keeps
        "KeyeConfig": lambda c: c.tiny(expert_shards=2, max_seq_len=MAX_SEQ, dtype="bfloat16")}
MODULES = getattr(llm_config, "SERVING_MODULES", None) or {
    kind: "ray_tpu.llm." + ("engine" if kind.__name__ == "LlamaConfig" else kind.__name__[:-len("Config")].lower() + "_serving")
    for kind in llm_config.ModelConfig.__args__}

def models(names):
    for kind, module in MODULES.items():
        tiny = TINY.get(kind.__name__, lambda c: c.tiny(max_seq_len=MAX_SEQ, dtype="bfloat16"))
        name = kind.__name__[:-len("Config")].lower()
        if name in names or not names:
            yield name, importlib.import_module(module), tiny(kind)

def run(backend, names=()):
    out = {}
    if backend == "mosaic":
        devices = topologies.get_topology_desc("v5e:2x2", "tpu").devices
        ctx = force_kernel_backend("mosaic", devices[0].device_kind)
        dev = NamedSharding(build_mesh(MeshSpec(), devices[:1]), P())
    else:
        ctx = force_kernel_backend("reference")
        dev = None
    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev) if dev is not None else jax.ShapeDtypeStruct(a.shape, a.dtype)
    def arg(shape, dtype=jnp.int32):
        return sds(jax.ShapeDtypeStruct(shape, dtype))
    with ctx:
        for name, module, cfg in models(names):
            served = getattr(module, "SERVED", None) or module.served_model(cfg)
            # the tree as the engine places it (``ServedModel.program_params``, which a tree from before PR 57 has not)
            placed = getattr(served, "program_params", None) or (lambda cfg, tree: tree)
            params = jax.tree.map(sds, jax.eval_shape(lambda: placed(cfg, served.init_params(cfg, jax.random.PRNGKey(0)))))
            cache = jax.tree.map(sds, jax.eval_shape(lambda: served.init_cache(cfg, SLOTS, MAX_SEQ)))
            # a burst's tokens: [slots], or [slots, K] where a step is a block of K positions
            token0 = arg((SLOTS,) if served.step is None else (SLOTS, served.step(cfg)[0]))
            if getattr(served, "pending_step", False):
                # with the step the line decided last and who has one (``ServedModel.pending_step``, since PR 63)
                token0 = (token0, token0, arg((SLOTS,), jnp.bool_))
            progs = {
              "prefill_chunk": (cfg, params, cache, arg((CHUNK,)), arg(()), arg(()), arg(())),
              "decode_step": (cfg, params, cache, arg((SLOTS,)), arg((SLOTS,)), arg((SLOTS,), jnp.bool_)),
              "decode_burst": (cfg, params, cache, token0, arg((SLOTS,)), arg((SLOTS,), jnp.bool_), arg((SLOTS,), jnp.float32), arg((SLOTS,), jnp.float32), arg((2,), jnp.uint32), BURST, False),
            }
            if served.decode_step is None:
                del progs["decode_step"]
            if served.mixed_burst is not None:
                # ``decode_burst``'s arguments with the riders after the key: a chunk a step, its slot, cached rows and length, and how many ride
                riders = (arg((4, CHUNK)), arg((4,)), arg((4,)), arg((4,)), arg(()))
                progs["mixed_burst"] = (*progs["decode_burst"][:9], riders, 4, False)
            for prog, args in progs.items():
                text = getattr(module, prog).lower(*args).as_text()
                out[f"{name}.{prog}.{backend}"] = hashlib.sha256(text.encode()).hexdigest()[:16]
                # outside the kernels' serialized bodies (which carry source lines)
                bare = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
                out[f"{name}.{prog}.{backend}.outside_kernels"] = hashlib.sha256(bare.encode()).hexdigest()[:16]
                fn = getattr(module, prog)
                static = tuple(i for i, a in enumerate(args) if not hasattr(a, "shape") and not isinstance(a, (dict, tuple)))
                jp = str(jax.make_jaxpr(fn, static_argnums=static)(*args))
                out[f"{name}.{prog}.{backend}.jaxpr"] = hashlib.sha256(jp.encode()).hexdigest()[:16]
                if os.environ.get("DUMP"):
                    open(os.path.join(os.environ["DUMP"], f"{name}.{prog}.{backend}.jaxpr.txt"), "w").write(jp)
                    open(os.path.join(os.environ["DUMP"], f"{name}.{prog}.{backend}.mlir.txt"), "w").write(bare)
    return out
if __name__ == "__main__":
    res = {}
    for b in ("reference", "mosaic"):
        res.update(run(b, sys.argv[1:]))
    print(json.dumps(res, indent=0, sort_keys=True))

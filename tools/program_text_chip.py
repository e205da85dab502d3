"""Are the serving cells' programs, LOWERED FOR THE CHIP, what they were?

The CPU suite holds the engines' programs at tiny presets to a digest
(``tests/test_hybrid.py``), but the cells time TPU-only branches (kernel
gates, ``paged_decode``, the experts' row tile) that only a lowering for the
chip takes: PR 26 was refused for a change to one that every CPU test
passed.  This compiles, for a DESCRIBED v5e and with no chip, the step
program and the narrowest and widest admission program of each serving
configuration's engine (published widths, a few layers, weights that are
shapes only) from the tree at ``<repo root>``, and writes each program's
text and sha256, source locations removed (those of the HLO and those
inside a kernel's serialized body), under ``<out dir>``:

    git archive <parent> | tar -x -C /root/scratch/parent
    python tools/program_text_chip.py /root/scratch/parent /root/scratch/a
    python tools/program_text_chip.py . /root/scratch/b
    diff /root/scratch/a/digests.json /root/scratch/b/digests.json

One run at a time (the TPU compiler's library is one process's), about six
minutes each.  Equal digests say "the same program"; nothing here runs."""
import hashlib
import inspect
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
repo, out_dir = sys.argv[1], sys.argv[2]
sys.path.insert(0, repo)
os.makedirs(out_dir, exist_ok=True)

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from paddle_tpu.framework import device as pdevice
from paddle_tpu.distributed import mesh as pmesh
from paddle_tpu import nn
from paddle_tpu.serving.generation import GenerationEngine
from benchmarks.harness import loader

import jax._src.tpu_custom_call as _tcc
from jax._src.lib.mlir import ir as _ir

_orig_asm = _tcc._lower_mosaic_module_to_asm


def _without_locations(module, **kw):
    # a kernel's serialized body carries the source lines of the kernel's
    # code: re-parse its text printed without them
    with module.context:
        module = _ir.Module.parse(
            module.operation.get_asm(enable_debug_info=False))
    return _orig_asm(module, **kw)


_tcc._lower_mosaic_module_to_asm = _without_locations

assert os.path.abspath(pdevice.__file__).startswith(
    os.path.abspath(repo)), pdevice.__file__
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = topo.devices[0]
pdevice.on_tpu = lambda: True
pmesh.set_mesh(pmesh.build_mesh(devices=[chip]))
jax.config.update("jax_enable_compilation_cache", False)
one = SingleDeviceSharding(chip)
_LOC = re.compile(
    r',?\s*(source_file="[^"]*"|(source_(end_)?(line|column)|stack_frame_id)'
    r'=\d+)|\n(FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*')
i32 = jnp.int32
bench = os.path.join(repo, "benchmarks")


def on_chip(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)


def ints(*shape):
    return jax.ShapeDtypeStruct(shape, i32, sharding=one)


def engine_of(config, traffic, layers):
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    if layers:
        cfg["num_hidden_layers"] = layers
    with open(os.path.join(bench, "traffic", traffic + ".json")) as f:
        buckets = json.load(f)["prompt_buckets"]
    fam = loader.load_module("families", cfg["family"], bench)
    serve = cfg["serve"]
    with nn.abstract_parameters():
        if cfg["family"] == "gpt":
            from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
            model = GPTForCausalLM(GPTConfig(dropout=0.0, num_layers=2))
        else:
            cls = {"joyai_flash": "LatentMoEForCausalLM",
                   "kimi_linear": "KimiLinearForCausalLM"}.get(
                       cfg["family"], "HybridForCausalLM")
            model = getattr(fam, cls)(fam.model_config(cfg))
    kw = {}
    if "cache_len" in serve:
        kw["cache_len"] = serve["cache_len"]
    return GenerationEngine(
        model, prompt_buckets=buckets, batch_size=serve["batch_size"],
        kv_page_size=serve["kv_page_size"], speculative_k=0,
        eos_token_id=None, name="t", **kw), buckets


out = {}
for config, traffic, layers in (("gpt2_small_serve", "docs_closed", 0),
                                ("gpt2_small_serve", "chat_open", 0),
                                ("joyai_flash_serve", "ragdocs_closed", 2),
                                ("olmo_hybrid_serve", "ragdocs_closed", 4),
                                ("qwen3_next_serve", "longgen_closed", 4),
                                ("k_exaone_serve", "ragdocs_closed", 4),
                                ("kimi_linear_serve", "longdoc_gen_closed",
                                 4)):
    if not os.path.exists(os.path.join(bench, "configs", config + ".json")):
        continue  # a tree from before the configuration
    eng, buckets = engine_of(config, traffic, layers)
    try:
        B, C, page = eng._batch, eng._C, eng._page
        G = C // page
        pool = on_chip(jax.eval_shape(eng._empty_pool))
        params, buffers = on_chip(eng._params), on_chip(eng._buffers)
        # since PR 34 the step takes the previous step's token column
        prev = ([ints(B, 1)] if "prev" in inspect.signature(
            eng._pstep).parameters else [])
        texts = {"step": eng._step_jit.lower(
            params, buffers, ints(B, 2 + C + G), *prev,
            pool).compile().as_text()}
        for sb in (buckets[0], buckets[-1]):
            R = eng._admit_rows[sb]
            texts[f"admit[{sb}]"] = eng._padmit.lower(
                params, buffers, ints(R, sb), ints(R, sb), ints(R, C),
                ints(R, G), ints(R), pool, None,
                ints(R) if eng._slot_state else None).compile().as_text()
    finally:
        eng.close()
    for k, t in texts.items():
        t = _LOC.sub("", t)
        name = f"{config}.{traffic}.{k}"
        with open(os.path.join(out_dir, name + ".txt"), "w") as f:
            f.write(t)
        out[name] = hashlib.sha256(t.encode()).hexdigest()
        print(name, out[name], t.count("tpu_custom_call"), flush=True)
with open(os.path.join(out_dir, "digests.json"), "w") as f:
    json.dump(out, f, indent=1)

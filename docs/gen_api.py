"""Generate the API reference (docs/api/*.md) from docstrings.

The reference ships a sphinx tree (~2k lines of .rst over autodoc);
here the docstrings are the single source of truth and this script
renders them to markdown — run it after changing public APIs:

    python docs/gen_api.py

Each top-level subpackage becomes one page listing every public symbol
(``__all__`` when defined, else underscore-filtered module globals)
with its signature and full docstring.  A symbol without a docstring is
reported as an error so the "every public symbol documented" invariant
is enforced, not aspirational.
"""

from __future__ import annotations

import dataclasses
import importlib
import inspect
import pathlib
import sys

PAGES = {
    "amp": ["apex_tpu.amp", "apex_tpu.amp.frontend", "apex_tpu.amp.lists",
            "apex_tpu.amp.o1"],
    "core": ["apex_tpu.core.precision", "apex_tpu.core.loss_scale",
             "apex_tpu.core.train_state", "apex_tpu.core.mesh"],
    "ops": ["apex_tpu.ops.attention", "apex_tpu.ops.paged_attention",
            "apex_tpu.ops.fused_sampling", "apex_tpu.ops.ssm",
            "apex_tpu.ops.expert_gmm",
            "apex_tpu.ops.multihead_attn",
            "apex_tpu.ops.layer_norm", "apex_tpu.ops.softmax",
            "apex_tpu.ops.rope", "apex_tpu.ops.mlp",
            "apex_tpu.ops.xentropy", "apex_tpu.ops.group_norm",
            "apex_tpu.ops.batch_norm", "apex_tpu.ops.autotune"],
    "optim": ["apex_tpu.optim.fused_adam", "apex_tpu.optim.fused_lamb",
              "apex_tpu.optim.fused_sgd", "apex_tpu.optim.fused_novograd",
              "apex_tpu.optim.fused_adagrad",
              "apex_tpu.optim.fused_mixed_precision_lamb",
              "apex_tpu.optim.larc", "apex_tpu.optim.clip",
              "apex_tpu.optim._multi_tensor"],
    "parallel": ["apex_tpu.parallel.ddp", "apex_tpu.parallel.sync_batchnorm",
                 "apex_tpu.parallel.ring_attention",
                 "apex_tpu.parallel.distributed_optim",
                 "apex_tpu.parallel.pipeline",
                 "apex_tpu.parallel.launch"],
    "plan": ["apex_tpu.plan", "apex_tpu.plan.costs",
             "apex_tpu.plan.enumerate", "apex_tpu.plan.score",
             "apex_tpu.plan.emit", "apex_tpu.plan.calibrate"],
    "transformer": ["apex_tpu.transformer.layers",
                    "apex_tpu.transformer.mappings",
                    "apex_tpu.transformer.cross_entropy",
                    "apex_tpu.transformer.random",
                    "apex_tpu.transformer.data",
                    "apex_tpu.transformer.moe",
                    "apex_tpu.transformer.microbatches",
                    "apex_tpu.transformer.parallel_state",
                    "apex_tpu.transformer.pipeline_parallel.schedules",
                    "apex_tpu.transformer.pipeline_parallel.build",
                    "apex_tpu.transformer.pipeline_parallel.p2p"],
    "contrib": ["apex_tpu.contrib", "apex_tpu.contrib.fmha",
                "apex_tpu.contrib.focal_loss",
                "apex_tpu.contrib.index_mul_2d",
                "apex_tpu.contrib.transducer", "apex_tpu.contrib.groupbn",
                "apex_tpu.contrib.conv_bias_relu",
                "apex_tpu.contrib.bottleneck",
                "apex_tpu.contrib.peer_memory",
                "apex_tpu.contrib.sparsity"],
    "models": ["apex_tpu.models.bert", "apex_tpu.models.gpt",
               "apex_tpu.models.vit", "apex_tpu.models.resnet",
               "apex_tpu.models.transformer",
               "apex_tpu.models.falcon_h1", "apex_tpu.models.afmoe",
               "apex_tpu.models.generate",
               "apex_tpu.models.torch_import"],
    "serving": ["apex_tpu.serving.api", "apex_tpu.serving.engine",
                "apex_tpu.serving.scheduler", "apex_tpu.serving.cache",
                "apex_tpu.serving.fleet"],
    "resilience": ["apex_tpu.resilience.faults",
                   "apex_tpu.resilience.checkpointing",
                   "apex_tpu.resilience.trainer"],
    "utils": ["apex_tpu.utils.checkpoint", "apex_tpu.utils.profiler",
              "apex_tpu.utils.debug", "apex_tpu.utils.metrics",
              "apex_tpu.utils.tree", "apex_tpu.utils.compile_cache",
              "apex_tpu.utils.lockcheck", "apex_tpu.utils.numcheck",
              "apex_tpu.utils.shardcheck"],
    "fp16_utils": ["apex_tpu.fp16_utils"],
    "data": ["apex_tpu.data"],
}


def _public_names(mod):
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return [n for n, v in vars(mod).items()
            if not n.startswith("_") and getattr(v, "__module__", None)
            == mod.__name__]


def _signature(obj):
    import re

    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return ""
    # strip live object addresses (sentinel defaults etc.) so the
    # generated docs are deterministic across machines/runs
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _render_symbol(name, obj, errors, qual):
    lines = []
    kind = ("class" if inspect.isclass(obj)
            else "function" if callable(obj) else "data")
    sig = _signature(obj) if kind != "data" else ""
    lines.append(f"### `{name}{sig}`\n")
    doc = inspect.getdoc(obj)
    if kind == "data" and type(obj).__module__ == "builtins" \
            and doc == inspect.getdoc(type(obj)):
        # a bare BUILTIN constant (str/int/tuple instance) "inherits"
        # its type's docstring through getdoc — boilerplate ("Create a
        # new string object..."), not documentation.  Project-class
        # singletons (e.g. metrics.counters) keep their class
        # docstring: for those the fallback IS the documentation.
        doc = None
    if not doc:
        if kind == "data":
            doc = f"*(module-level data: `{type(obj).__name__}`)*"
        else:
            errors.append(qual)
            doc = "**UNDOCUMENTED**"
    lines.append(doc + "\n")
    if inspect.isclass(obj):
        if dataclasses.is_dataclass(obj):
            fields = ", ".join(
                f"`{f.name}`" for f in dataclasses.fields(obj))
            if fields:
                lines.append(f"*Fields:* {fields}\n")
        for mname, m in sorted(vars(obj).items()):
            if mname.startswith("_") or not callable(m):
                continue
            mdoc = inspect.getdoc(m)
            if mdoc:
                first = mdoc.splitlines()[0]
                lines.append(
                    f"- **`.{mname}{_signature(m)}`** — {first}")
        lines.append("")
    return "\n".join(lines)


def main():
    out_dir = pathlib.Path(__file__).parent / "api"
    out_dir.mkdir(exist_ok=True)
    errors = []
    index = ["# API reference\n",
             "Generated from docstrings by `python docs/gen_api.py` — "
             "regenerate after public-API changes.\n"]
    for page, modules in PAGES.items():
        parts = [f"# `apex_tpu` API — {page}\n"]
        for modname in modules:
            mod = importlib.import_module(modname)
            parts.append(f"## module `{modname}`\n")
            mdoc = inspect.getdoc(mod)
            if mdoc:
                parts.append(mdoc + "\n")
            else:
                errors.append(modname)
            for name in _public_names(mod):
                obj = getattr(mod, name)
                parts.append(_render_symbol(
                    name, obj, errors, f"{modname}.{name}"))
        (out_dir / f"{page}.md").write_text("\n".join(parts))
        index.append(f"- [{page}]({page}.md)")
    (out_dir / "index.md").write_text("\n".join(index) + "\n")
    if errors:
        print("UNDOCUMENTED public symbols:", file=sys.stderr)
        for e in errors:
            print(f"  {e}", file=sys.stderr)
        sys.exit(1)
    n = sum(1 for _ in out_dir.glob("*.md"))
    print(f"wrote {n} pages to {out_dir}")


if __name__ == "__main__":
    main()

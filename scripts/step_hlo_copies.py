#!/usr/bin/env python3
"""What a configuration's step programs MOVE that no user asked for: every
`copy` and every materialised `dynamic-slice` or `slice` of at least `--min-mb`
in the programs the engine jits for it, compiled for a DESCRIBED TPU v5e — no
chip:

    python scripts/step_hlo_copies.py benchmarks/configs/<name>.json \
        [--min-mb 32] [--tokens N] [--rehearse] [--default-layouts] \
        [--dump DIR] [--by-scope] [--text DIR]

A weight that the compiler reads in another order than it is stored in is
re-laid once a launch of the program: `copy.126 bf16[6,1536,24576]`, the whole
`mla_wuq` stack, was 1.40 ms of openPangu's 15 ms pass (PERF.md section 6,
PR 45) and shows here as a `copy` whose result has the operand's shape and
another layout; K-EXAONE's ragged step, its weights row-major, moves a layer
of `wq` TEN times a step — a slice and a copy a layer: in the loop of two
layers `constant_dynamic-slice_fusion.7 bf16[1,6144,8192]` and `copy.195` of
it to `{1,2,0}`, for the three layers outside any loop
`slice_bitcast_fusion#0..2` and three `copy bf16[8192,6144]` `{0,1}` ->
`{1,0}` under `attn_qkv` — and as served the five slices alone (PR 51). A
layer's slice of a stack that a kernel or a contraction cannot read in place
shows as a `...dynamic-slice_fusion` of the layer's shape inside a run's
loop, and as one result of a `slice` fusion (`fusion.698#1`) for the layers a
run of ONE repeat leaves outside any loop: three of K-EXAONE's five, 0.92 ms
of its 16.4 ms step where the loop's two are 0.27.

The configuration file's keys make the `ModelConfig` and its `server_flags`
the pool and the step shapes, as `benchmarks/serve.py` hands them to the CLI;
the programs are the engine's own (`engine/step_program.py`: `ragged_step` at
`--max-batch-tokens`, with the prediction module's carries under `--spec`;
`decode_scan` at `--decode-steps` otherwise), lowered with the weights'
shapes in the formats `models/llama.py:weight_formats` names — the latent
up-projections, and `wq` / `wk` where `_qkv` splits its projections into heads
at once; nothing for a model that norms them flat first, whose compiler reads
them row-major (`--default-layouts`: every weight in row-major order) and
`--tp` over a mesh of the described chips. Nothing runs: a compile that
passes is not a chip run, and an op listed here has no time until a trace
gives it one. One JSON line a program, then one last line with the count.
`--rehearse` compiles the file's tiny `rehearse` sizes (seconds; sizes no
copy of 32 MB exists at: give `--min-mb 0`). `--by-scope` adds to a program's
line the compiler's own `estimated_cycles` summed by the model's stage
(`jax.named_scope`: `lin_conv`, `mlp`, ...) a computation — the largest is
one period of the layers' loop: what the compiler THINKS a stage costs (1.5
cycles a ns), before any trace; against one it read the hybrid's matmuls
1.3 x high, its window's gathers 1.2-1.7 x and its slices and in-place updates
3-4 x (PERF.md section 5, PR 53).

Not on the serving path: nothing imports this module.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import re
import sys
from typing import NamedTuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
            "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "f32": 4, "s32": 4,
            "u32": 4, "f64": 8, "s64": 8, "u64": 8}
# `%name = dtype[dims]{layout} opcode(operands...)`, with or without ROOT.
_INSTR = re.compile(
    r"^\s*(?P<root>ROOT )?%(?P<name>[\w.\-]+) = (?P<dtype>\w+)"
    r"\[(?P<dims>[\d,]*)\](?P<layout>\{[^}]*\})? (?P<op>[\w\-]+)"
    r"\((?P<args>[^)]*)\)")
# a fusion with a tuple for its result: `%name = (shape, shape, ...) fusion(`
_TUPLE_FUSION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = \((?P<shapes>.*)\) fusion"
    r"\((?P<args>[^)]*)\)")
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\](\{[^}]*\})?")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(?P<name>[\w.\-]+) \(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
MOVES = ("copy", "dynamic-slice", "slice")
_CYCLES = re.compile(r'"estimated_cycles":"?(\d+)')
_ANY_INSTR = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = .*? ([\w\-]+)\(")
FREE = ("parameter", "get-tuple-element", "tuple", "bitcast", "constant")


def moves(hlo: str, min_bytes: int) -> list:
    """The device ops of a compiled module's text that only MOVE at least
    `min_bytes`: a `copy`, a `dynamic-slice`, a `slice`, or a fusion whose
    root is one (XLA names it `...dynamic-slice_fusion`, `copy_fusion`,
    `slice_bitcast_fusion`) or a tuple of them (the layers a run leaves
    outside its loop, sliced out of a stack in ONE fusion: an entry a
    result, `name#i`). Only an instruction of the entry, a loop's body or a
    branch is an op of its own on the device: one inside a fused computation
    is part of that fusion's read (a contraction that re-lays its operand as
    it reads it) and is not listed. Each with its name, which of the three it
    `moves`, shape, the result's layout, the first operand's name (a
    parameter's says which weight), shape and layout, the `op_name` the
    source gave it, and the computation it is an instruction `of` (a
    conditional's branch, by name: `branches`)."""
    computations, tuples, tuple_roots = {}, {}, {}
    at, fused = None, set()
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            at = head["name"]
            computations.setdefault(at, [])
            tuples.setdefault(at, [])
            continue
        if " fusion(" in line:  # also one with a tuple for its result
            fused.update(_CALLS.findall(line))
        if at is None:
            continue
        if "ROOT " in line and " tuple(" in line:
            tuple_roots[at] = line
        elif (m := _INSTR.match(line)) is not None:
            computations[at].append((m, line))
        elif (m := _TUPLE_FUSION.match(line)) is not None:
            tuples[at].append((m, line))

    def root_ops(computation: str) -> list:
        """What a fused computation's root does, through bitcasts: one op,
        or one a result where the root is a tuple."""
        instrs = computations.get(computation, ())
        by_name = {m["name"]: m for m, _ in instrs}

        def through(m):
            while m is not None and m["op"] == "bitcast":
                first = re.search(r"%([\w.\-]+)", m["args"])
                m = by_name.get(first.group(1)) if first else None
            return m["op"] if m is not None else ""

        root = tuple_roots.get(computation)
        if root is not None:
            return [through(by_name.get(n)) for n in re.findall(
                r"%([\w.\-]+)", root.split(" tuple(", 1)[1].split(")")[0])]
        return [through(next((m for m, _ in instrs if m["root"]), None))]

    found = []
    for name, instrs in computations.items():
        if name in fused:
            continue
        defined = {m["name"]: (f"{m['dtype']}[{m['dims']}]",
                               m["layout"] or "") for m, _ in instrs}
        results = [(m["name"], m["op"], [(m["dtype"], m["dims"],
                                         m["layout"])], m["args"], line)
                   for m, line in instrs]
        results += [(m["name"], "fusion", _SHAPE.findall(m["shapes"]),
                     m["args"], line) for m, line in tuples[name]]
        for instr, op, shapes, args, line in results:
            kinds = [op] * len(shapes) if op != "fusion" else [
                k for c in _CALLS.findall(line) for k in root_ops(c)]
            first = re.search(r"%([\w.\-]+)", args)
            src = defined.get(first.group(1) if first else "", ("?", ""))
            op_name = _OP_NAME.search(line)
            for i, ((dtype, dim, layout), kind) in enumerate(
                    zip(shapes, kinds)):
                dims = [int(d) for d in dim.split(",") if d]
                n = math.prod(dims) * ITEMSIZE.get(dtype, 0)
                if n < max(min_bytes, 1) or kind not in MOVES:
                    continue
                found.append({
                    "name": instr + (f"#{i}" if len(shapes) > 1 else ""),
                    "op": op, "moves": kind,
                    "shape": f"{dtype}[{dim}]", "dims": dims,
                    "layout": layout or "", "bytes": n,
                    "from": first.group(1) if first else "",
                    "from_shape": src[0], "from_layout": src[1],
                    "op_name": op_name.group(1) if op_name else "",
                    "of": name})
    return found


def branches(hlo: str) -> dict:
    """{conditional: its branch computations' names, in index order} of a
    compiled module's text — `lax.cond`'s false branch is index 0. An op that
    `moves` names the computation it is `of`: one inside a branch runs only
    on the steps that take it."""
    out = {}
    for line in hlo.splitlines():
        m = _ANY_INSTR.match(line)
        if m is None or m.group(1) != "conditional":
            continue
        name = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = ", line).group(1)
        listed = re.search(r"branch_computations=\{([^}]*)\}", line)
        out[name] = re.findall(r"%([\w.\-]+)", listed.group(1))
    return out


def scope_cycles(hlo: str, scopes, at_least: int = 20_000) -> dict:
    """{computation: {scope: [estimated cycles, ops, ops the compiler gave
    no estimate]}} over the device ops of a compiled module's text (not the
    insides of fusions), an op under the innermost of `scopes` its `op_name`
    passes through ("-": none); computations of under `at_least` cycles
    left out."""
    fused = {c for line in hlo.splitlines() if " fusion(" in line
             for c in _CALLS.findall(line)}
    out, at = {}, None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            at = head["name"]
            continue
        m = _ANY_INSTR.match(line)
        if at is None or at in fused or m is None or m.group(1) in FREE:
            continue
        name = _OP_NAME.search(line)
        inside = [p for p in (name.group(1) if name else "").split("/")
                  if p in scopes]
        cycles = _CYCLES.search(line)
        cell = out.setdefault(at, {}).setdefault(
            inside[-1] if inside else "-", [0, 0, 0])
        cell[0] += int(cycles.group(1)) if cycles else 0
        cell[1] += 1
        cell[2] += cycles is None
    return {c: by for c, by in out.items()
            if sum(v[0] for v in by.values()) >= at_least}


def weight_copies(found: list, params) -> list:
    """Those of `found` that re-lay a weight: a `copy` whose result has the
    logical shape of a stack of `params["layers"]` (the loop's re-layout
    hoisted out of it) or of one layer of a stack (left inside), each with
    the `stacks` of that shape."""
    named = {}
    for name, leaf in params["layers"].items():
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) >= 3:
            named.setdefault(shape, []).append(name)
            named.setdefault((1, *shape[1:]), []).append(name)
    return [{"name": f["name"], "stacks": named[tuple(f["dims"])]}
            for f in found
            if f["moves"] == "copy" and tuple(f["dims"]) in named]


_MOSAIC_BODY = re.compile(r'(\\22body\\22: \\22)[^\\]*(\\22)')


def masked_text(lowered) -> str:
    """A lowered program's StableHLO text with every Mosaic kernel's
    serialised body masked: the body carries its source file's PATH and
    LINES, so two checkouts, or an edit above a kernel, never give the same
    bytes for the same kernel (compare the kernels' `jax.make_jaxpr` strings
    beside it after an edit to a kernel file)."""
    return _MOSAIC_BODY.sub(r"\1MASKED\2", lowered.as_text())


def describe_v5e():
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


class StepArgs(NamedTuple):
    """The abstract arguments of a configuration's step programs (`step_args`)
    and the mesh they are sharded over (None: one chip)."""
    mesh: object
    params: dict
    carried: tuple  # the two pools, `recent`, `last_ids`, the per-slot state
    drafts: tuple  # a prediction module's two carries, else ()
    buf: object  # (words) -> the packed host input's (step_pack)

    def lower(self, fn, words: int, drafts: bool = True):
        """`fn`, a jit of `engine/step_program.py`, lowered for them."""
        return fn.lower(self.params, self.buf(words), *self.carried,
                        *(self.drafts if drafts else ()))

    @property
    def carried_bytes(self) -> int:
        import jax

        return sum(a.size * a.dtype.itemsize for a in
                   jax.tree_util.tree_leaves((self.carried, self.drafts)))


def step_args(mc, dims, devices, *, num_pages: int, ring_tokens: int,
              tp: int = 1, mtp: bool = False,
              default_layouts: bool = False) -> StepArgs:
    """What a runtime of `mc` hands its step programs, as
    `ShapeDtypeStruct`s on the described `devices`: the weights (in the
    formats `models/llama.py:weight_formats` names unless `default_layouts`;
    sharded over a `tp` mesh), the two pools of `num_pages` pages — K and V
    rows, or a latent-attention model's latent rows and index keys: two
    pools of different widths (`ModelConfig.kv_row_dims`) —, the penalty
    ring, the id carry, the per-slot state (its rings sized for a stream of
    `ring_tokens`; None, no leaf, for a model that keeps none) and with
    `mtp` the prediction module's drafts and its rows' lengths. `dims`:
    `step_program.StepDims`. The ONE place they are built:
    `tests/chip_compile.py` lowers through it too."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import (NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from ollamamq_tpu.models import llama
    from ollamamq_tpu.parallel.mesh import make_mesh
    from ollamamq_tpu.parallel.sharding import (kv_cache_spec,
                                                param_partition_specs)

    S, ps = dims.max_slots, dims.page_size
    shapes = jax.eval_shape(
        lambda: llama.init_params(mc, jax.random.PRNGKey(0)))
    mesh = None
    if tp > 1:
        mesh = make_mesh(tp=tp, devices=devices[:tp])
        rep = NamedSharding(mesh, PartitionSpec())
        pool_sharding = NamedSharding(mesh, kv_cache_spec())
        param_shardings = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec),
            param_partition_specs(shapes))
    else:
        rep = pool_sharding = SingleDeviceSharding(devices[0])
        param_shardings = jax.tree_util.tree_map(lambda _: rep, shapes)

    def s(shape, dt=jnp.int32, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    params = jax.tree_util.tree_map(
        lambda a, sh: s(a.shape, a.dtype, sh), shapes, param_shardings)
    if not default_layouts:
        for name, fmt in llama.weight_formats(mc, params).items():
            leaf = params["layers"][name]
            params["layers"][name] = s(leaf.shape, leaf.dtype, fmt)
    pools = tuple(
        s((mc.cache_layers, num_pages * ps, lanes), jnp.bfloat16,
          pool_sharding) for lanes in mc.kv_row_dims)
    state = jax.tree_util.tree_map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: llama.alloc_slot_state(
            mc, S, ring_rows=mc.ring_rows(ring_tokens, ps),
            pooled_rows=mc.pooled_rows(num_pages, ps))))
    return StepArgs(
        mesh, params,
        (*pools, s((S + 1, dims.repeat_last_n)), s((S,)), state),
        (s((S + 1,)),) * 2 if mtp else (), lambda words: s((words,)))


def step_programs(mc, flags, topo, tokens: int,
                  default_layouts: bool = False):
    """{jit name: jax.stages.Lowered} of the step programs a server of
    `mc` under the CLI flags `flags` (an argparse namespace of
    `cli.build_parser`) launches in its steady state, for the described
    chips of `topo`, the ragged step at a stream of `tokens`; and the
    abstract params they were lowered with."""
    from ollamamq_tpu.engine import step_program
    from ollamamq_tpu.engine.engine import EngineConfig, ragged_budget

    dims = step_program.StepDims(
        flags.page_size, flags.max_slots, flags.max_pages_per_seq,
        EngineConfig.repeat_last_n)
    mtp = bool(flags.spec and mc.num_nextn_predict_layers)
    args = step_args(mc, dims, topo.devices, num_pages=flags.num_pages,
                     ring_tokens=ragged_budget(flags), tp=flags.tp, mtp=mtp,
                     default_layouts=default_layouts)
    every = (True, True, True)  # penalties, masks, sampling: the superset
    built = dict(attn_impl="pallas", mesh=args.mesh)
    out = {"mq_ragged_step": args.lower(
        step_program.ragged_step(mc, dims, tokens, 1 if mtp else 0, every,
                                 mtp=mtp, **built),
        dims.ragged_layout(tokens).size)}
    if not flags.spec:
        out["mq_decode_scan"] = args.lower(
            step_program.decode_scan(mc, dims, flags.decode_steps, every,
                                     **built),
            dims.decode_layout().size)
    return out, args.params


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("config", help="a benchmarks/configs/*.json file")
    ap.add_argument("--min-mb", type=float, default=32.0)
    ap.add_argument("--tokens", type=int,
                    help="the ragged step's stream (default: the file's "
                    "--max-batch-tokens; a pass of decode rows is --max-slots"
                    " tokens, of verify spans twice that)")
    ap.add_argument("--rehearse", action="store_true",
                    help="the file's tiny `rehearse` sizes")
    ap.add_argument("--default-layouts", action="store_true",
                    help="every weight row-major, not as served")
    ap.add_argument("--dump", help="write each program's compiled HLO here")
    ap.add_argument("--by-scope", action="store_true",
                    help="add the compiler's estimated cycles by stage")
    ap.add_argument("--text", metavar="DIR",
                    help="compile nothing: write each program's lowered "
                    "StableHLO text there, the Mosaic bodies masked, and "
                    "print its sha256 (did a change move a step program? run "
                    "both trees AT ONE PATH and diff the directories)")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import serve
    from ollamamq_tpu import cli
    from ollamamq_tpu.models import llama, moe

    with open(args.config) as f:
        cfg = json.load(f)
    mc = serve.model_config(cfg, args.rehearse)
    flags = cli.build_parser().parse_args(
        ["--models", cfg["name"]] + serve.server_flags(cfg, args.rehearse))
    jax.config.update("jax_enable_compilation_cache", False)
    topo = describe_v5e()
    lowered, params = step_programs(
        mc, flags, topo, args.tokens or flags.max_batch_tokens,
        args.default_layouts)
    if args.text:
        os.makedirs(args.text, exist_ok=True)
        for name, low in lowered.items():
            text = masked_text(low)
            with open(os.path.join(
                    args.text, f"{cfg['name']}.{name}.stablehlo.txt"),
                    "w") as f:
                f.write(text)
            print(json.dumps({
                "config": cfg["name"], "program": name,
                "sha256": hashlib.sha256(text.encode()).hexdigest()}))
        return 0
    n_weight = 0
    for name, low in lowered.items():
        hlo = low.compile().as_text()
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            with open(os.path.join(
                    args.dump, f"{cfg['name']}.{name}.hlo.txt"), "w") as f:
                f.write(hlo)
        found = moves(hlo, int(args.min_mb * 2 ** 20))
        weights = weight_copies(found, params)
        n_weight += len(weights)
        line = {"config": cfg["name"], "program": name, "moves": found,
                "weight_copies": weights}
        if args.by_scope:
            line["scope_cycles"] = scope_cycles(hlo, (
                *llama.SCOPES, *llama.GATE_SCOPES, *llama.MTP_SCOPES,
                *llama.CONV_SCOPES, *llama.LINEAR_SCOPES, *llama.SSM_SCOPES,
                *llama.HYBRID_SCOPES, *moe.SCOPES))
        print(json.dumps(line), flush=True)
    print(json.dumps({"config": cfg["name"], "programs": len(lowered),
                      "weight_copies": n_weight}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

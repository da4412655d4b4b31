"""Names on the train step, the tracer's spans on the profiler's clock, the
compile counter, and the record a stopped trace leaves behind (CPU, tiny
sizes)."""

import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphafold2_tpu.observe import profiler as profiler_mod
from alphafold2_tpu.observe import tracing
from alphafold2_tpu.observe.tracing import Tracer, compile_counts
from benchmark.harness import scope_reduce

BLOCKS = ("pair_axial", "msa_axial", "pair_from_msa", "msa_from_pair",
          "pair_ff", "msa_ff")


def tiny_config(depth=2, features="msa", **train):
    """The flagship's shape of step (tied rows, depth 2, bf16) at toy
    sizes. (``features="none"``, sequence only, halves the eager init.)"""
    from alphafold2_tpu.config import (
        Config, DataConfig, ModelConfig, TrainConfig,
    )

    return Config(
        model=ModelConfig(dim=32, depth=depth, heads=2, dim_head=16,
                          max_seq_len=32, msa_tie_row_attn=True,
                          bfloat16=True),
        data=DataConfig(crop_len=16, msa_depth=2, msa_len=16, batch_size=1,
                        min_len_filter=16, features=features),
        train=TrainConfig(gradient_accumulate_every=1, warmup_steps=2,
                          num_steps=6, log_every=100, **train),
    )


# ------------------------------------------------------ (a) the names ---


def heavy_op_scopes(lowered) -> list:
    """The ``op_name`` of every dot, convolution and custom call of a
    lowered program (StableHLO with debug locations)."""
    text = lowered.as_text(debug_info=True)
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    found = []
    for ref in re.findall(
            r"stablehlo\.(?:dot_general|convolution|custom_call)\b"
            r".*loc\((#loc\d+)\)", text):
        name = re.match(r'"([^"]*)"', locs[ref])
        found.append(name.group(1) if name else "")
    return found


@pytest.fixture(scope="module")
def lowered_step():
    from alphafold2_tpu.data.pipeline import make_dataset
    from alphafold2_tpu.train.loop import (
        build_model, device_put_batch, make_train_step, tiny_init_state,
    )

    cfg = tiny_config()
    sample = next(iter(make_dataset(cfg.data, seed=0)))
    model = build_model(cfg)
    # shapes are enough to lower: no init program is compiled
    state = jax.eval_shape(lambda: tiny_init_state(cfg, model, sample))
    return make_train_step(model).lower(
        state, device_put_batch(sample), jax.random.key(1))


def test_every_heavy_operation_of_the_step_is_under_a_named_scope(
        lowered_step):
    scopes = heavy_op_scopes(lowered_step)
    assert len(scopes) > 100
    seen = set()
    for scope in scopes:
        group, way, block, named = scope_reduce.classify(scope)
        assert named, f"no block or phase scope on {scope!r}"
        assert profiler_mod.block_of(scope) != "unscoped", scope
        if group != scope_reduce.OUTSIDE_MODEL:
            assert way in ("fwd", "bwd"), scope
        seen.add((way, block))
    # forward and backward of every trunk block, told apart by name (the
    # last layer's MSA update feeds nothing, but layer 0's is there)
    for block in BLOCKS:
        assert ("fwd", block) in seen and ("bwd", block) in seen, block
    assert ("fwd", "loss") in seen  # the labels' einsum


def test_phases_around_the_model_are_named_in_the_compiled_step(
        lowered_step):
    """loss, grads_ok, grad_clip (inside optimizer), optimizer and metrics
    reach the compiled program's metadata, which the trace is joined on."""
    module, scopes = profiler_mod.instruction_scopes(
        lowered_step.compile().as_text())
    assert module == "jit_step"
    blocks = {profiler_mod.block_of(s) for s in scopes.values()}
    for phase in ("fwd/loss", "grads_ok", "grad_clip", "optimizer",
                  "metrics"):
        assert phase in blocks, (phase, sorted(blocks))


@pytest.mark.parametrize("scope, expected", [
    ("jit(step)/jvp(Alphafold2)/trunk/layer_0/pair_from_msa/to_q/dot_general",
     ("cross_attn", "fwd", "pair_from_msa", True)),
    ("jit(step)/transpose(jvp(Alphafold2))/trunk/layer_11/msa_ff/wi/dot",
     ("feedforward", "bwd", "msa_ff", True)),
    # the ring's scopes sit under the Flax module's: the block is still the
    # first name after layer_N, forward and under the ring's custom VJP
    ("jit(step)/jvp(Alphafold2)/trunk/layer_0/pair_from_msa/shard_map/"
     "ring_block/flash_attention/pallas_call",
     ("cross_attn", "fwd", "pair_from_msa", True)),
    ("jit(step)/transpose(jvp(Alphafold2))/trunk/layer_0/msa_from_pair/"
     "shard_map/ring_merge/ppermute",
     ("cross_attn", "bwd", "msa_from_pair", True)),
    ("jit(step)/jvp(Alphafold2)/trunk/layer_1/pair_axial_norm/mul",
     ("model_rest", "fwd", "pair_axial_norm", True)),
    ("jit(step)/jvp(Alphafold2)/distogram_proj/dot_general",
     ("model_rest", "fwd", "distogram_proj", True)),
    ("jit(step)/transpose(jvp(loss))/mul",
     ("outside_model", "bwd", "loss", True)),
    ("jit(step)/optimizer/grad_clip/mul",
     ("outside_model", "", "grad_clip", True)),
    ("jit(_threefry_split)", ("outside_model", "", "jit(_threefry_split)",
                              True)),
    ("jit(step)/mul", ("outside_model", "", "unscoped", False)),
    ("state.params['params']['trunk']", ("outside_model", "", "unscoped",
                                         False)),
    ("", ("outside_model", "", "unscoped", False)),
])
def test_classify_a_scope(scope, expected):
    assert scope_reduce.classify(scope) == expected
    # the program's own table uses the same rows
    way, block = expected[1], expected[2]
    assert profiler_mod.block_of(scope) == (
        f"{way}/{block}" if way else block)


@pytest.mark.parametrize("scope,block", [
    ("jit(step)/jvp(MlaMoeLM)/layer_2/mla_attn/core/pallas_call",
     "fwd/mla_attn"),
    # the splash kernels of the causal core, forward, recomputed and backward
    ("jit(step)/jvp(MlaMoeLM)/layer_0/mla_attn/core/vmap(jit(_splash_"
     "attention))/splash_mha_fwd_residuals/splash_mha_fwd_residuals/"
     "pallas_call", "fwd/mla_attn"),
    ("jit(step)/transpose(jvp(MlaMoeLM))/jvp(MlaMoeLM)/checkpoint/"
     "rematted_computation/layer_4/mla_attn/core/vmap(jit(_splash_"
     "attention))/splash_mha_fwd_residuals/splash_mha_fwd_residuals/"
     "pallas_call", "bwd/mla_attn"),
    ("jit(step)/transpose(jvp(MlaMoeLM))/jvp(MlaMoeLM)/checkpoint/layer_4/"
     "mla_attn/core/vmap(jit(_splash_attention))/splash_mha_dkv_no_residuals"
     "/splash_mha_dkv_no_residuals/pallas_call", "bwd/mla_attn"),
    ("jit(step)/transpose(jvp(MlaMoeLM))/jvp(MlaMoeLM)/checkpoint/"
     "rematted_computation/layer_3/moe/experts/mul", "bwd/moe"),
    ("jit(step)/transpose(jvp(MlaMoeLM))/jvp(MlaMoeLM)/checkpoint/layer_0/"
     "dense_ffn/up_proj/dot_general", "bwd/dense_ffn"),
    ("jit(step)/jvp(MlaMoeLM)/embed/jit(_take)/gather", "fwd/embed"),
    ("jit(step)/transpose(jvp(MlaMoeLM))/head/dot_general", "bwd/head"),
    ("jit(step)/jvp(loss)/reduce_sum", "fwd/loss"),
    ("ragged-dot-none", "unscoped"),  # XLA's own name for the kernel
])
def test_the_programs_table_names_another_models_blocks(scope, block):
    """The log line's rows are the model's own modules whatever the model is
    called: what ``jvp(`` wraps, when it is no phase of the step."""
    assert profiler_mod.block_of(scope) == block


def test_an_instruction_that_runs_over_lines_keeps_its_scope():
    """The splash kernels' frontend attributes hold newlines, so the
    compiled text breaks such an instruction over three lines with its
    metadata on the last: the trace is joined on that ``op_name`` all the
    same, and an instruction without one takes none from its neighbours."""
    text = """HloModule jit_step, entry_computation_layout={()->f32[]}

ENTRY %main () -> f32[] {
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%p.1)
  %splash_mha_fwd_residuals.10 = (f32[2,1024,128]{2,1,0}, bf16[2,32,8192,128]{3,2,1,0}) custom-call(%copy-done.583), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 1024, \\"block_kv\\": 1024}"
}}, metadata={op_name="jit(step)/jvp(MlaMoeLM)/layer_0/mla_attn/core/vmap(jit(_splash_attention))/splash_mha_fwd_residuals/pallas_call" stack_frame_id=104}, backend_config={}
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  ROOT %fusion.2 = f32[] fusion(%copy-done.1), kind=kLoop, metadata={op_name="jit(step)/jvp(loss)/reduce_sum"}
}
"""
    module, scopes = profiler_mod.instruction_scopes(text)
    assert module == "jit_step"
    assert scopes == {
        "splash_mha_fwd_residuals.10":
            "jit(step)/jvp(MlaMoeLM)/layer_0/mla_attn/core/vmap(jit(_splash_"
            "attention))/splash_mha_fwd_residuals/pallas_call",
        "fusion.2": "jit(step)/jvp(loss)/reduce_sum",
    }
    assert profiler_mod.block_of(
        scopes["splash_mha_fwd_residuals.10"]) == "fwd/mla_attn"


# ------------------------------ (a') scopes the compiled step does not give

LM = "jit(step)/jvp(M)"
# One of each case (the numbers in the names are arbitrary): a fusion without
# a name whose body has one; a move between memory spaces, two steps from
# the kernel it feeds; a kernel whose text runs over three lines; a layout
# copy between an unnamed producer and a named consumer; two kernels under
# XLA's own name, one feeding the experts' product, one feeding combine; a
# copy that only the result's tuple consumes; a copy nothing reaches.
UNNAMED_TEXT = """HloModule jit_step, entry_computation_layout={(f32[8]{0}, f32[8]{0})->(f32[8]{0}, f32[8]{0})}

%fused_computation.2 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %add.0 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(step)/jvp(M)/layer_0/mla_attn/rope/add" stack_frame_id=3}
  %mul.1 = f32[8]{0} multiply(%add.0, %param_0), metadata={op_name="jit(step)/jvp(M)/layer_0/mla_attn/rope/mul" stack_frame_id=3}
  ROOT %mul.2 = f32[8]{0} multiply(%mul.1, %mul.1), metadata={op_name="jit(step)/jvp(M)/layer_0/mla_attn/rope/mul" stack_frame_id=3}
}

ENTRY %main.1 (p.1: f32[8], p.2: f32[8]) -> (f32[8], f32[8]) {
  %p.1 = f32[8]{0} parameter(0), metadata={op_name="state.params['x']"}
  %p.2 = f32[8]{0} parameter(1)
  %fusion.2 = f32[8]{0:T(1024)} fusion(%p.1), kind=kLoop, calls=%fused_computation.2
  %copy-start.1 = (f32[8]{0:T(1024)S(1)}, f32[8]{0:T(1024)}, u32[]{:S(2)}) copy-start(%fusion.2)
  %copy-done.1 = f32[8]{0:T(1024)S(1)} copy-done(%copy-start.1)
  %splash_mha_fwd_residuals.10 = (f32[8]{0}, f32[8]{0}) custom-call(%copy-done.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 1024, \\"block_kv\\": 1024}"
}}, metadata={op_name="jit(step)/jvp(M)/layer_0/mla_attn/core/pallas_call" stack_frame_id=104}, backend_config={}
  %get-tuple-element.3 = f32[8]{0} get-tuple-element(%splash_mha_fwd_residuals.10), index=0
  %bitcast.6 = f32[8]{0} bitcast(%p.2)
  %copy.7 = f32[8]{0:T(256)} copy(%bitcast.6)
  %fusion.8 = f32[8]{0} fusion(%copy.7, %get-tuple-element.3), kind=kLoop, calls=%fused_computation.8, metadata={op_name="jit(step)/jvp(M)/layer_1/moe/dispatch/gather" stack_frame_id=7}
  %ragged-dot-none.1 = f32[8]{0} custom-call(%fusion.8, /*index=1*/%p.2), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.11 = f32[8]{0} fusion(%ragged-dot-none.1), kind=kLoop, calls=%fused_computation.11, metadata={op_name="jit(step)/jvp(M)/layer_1/moe/experts/mul" stack_frame_id=8}
  %ragged-dot-none.2 = f32[8]{0} custom-call(%fusion.11, /*index=1*/%p.2), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.12 = f32[8]{0} fusion(%ragged-dot-none.2), kind=kLoop, calls=%fused_computation.12, metadata={op_name="jit(step)/jvp(M)/layer_1/moe/combine/mul" stack_frame_id=9}
  %copy.13 = f32[8]{0} copy(%fusion.12)
  %copy.9 = f32[8]{0} copy(%p.2)
  ROOT %tuple.14 = (f32[8]{0}, f32[8]{0}) tuple(%copy.13, %copy.9)
}
"""


@pytest.mark.parametrize("instruction, scope, rule", [
    ("fusion.2", f"{LM}/layer_0/mla_attn/rope/mul", "body"),
    ("copy-start.1", f"{LM}/layer_0/mla_attn/core/pallas_call", "user"),
    ("copy-done.1", f"{LM}/layer_0/mla_attn/core/pallas_call", "user"),
    # read from the text's third line of it, and never replaced
    ("splash_mha_fwd_residuals.10",
     f"{LM}/layer_0/mla_attn/core/pallas_call", None),
    ("get-tuple-element.3", f"{LM}/layer_1/moe/dispatch/gather", "user"),
    ("copy.7", f"{LM}/layer_1/moe/dispatch/gather", "user"),
    ("fusion.8", f"{LM}/layer_1/moe/dispatch/gather", None),
    # the experts' two products: the first feeds their own multiply, the
    # second feeds combine and is fed by that multiply
    ("ragged-dot-none.1", f"{LM}/layer_1/moe/experts/ragged-dot-none", "kin"),
    ("ragged-dot-none.2", f"{LM}/layer_1/moe/experts/ragged-dot-none", "kin"),
    ("fusion.12", f"{LM}/layer_1/moe/combine/mul", None),
    ("copy.13", f"{LM}/layer_1/moe/combine/mul", "operand"),
    ("copy.9", "", None),  # between an argument and the result: nothing near
])
def test_a_scope_is_inferred_where_the_compiled_step_gives_none(
        instruction, scope, rule):
    module, graph = profiler_mod.instruction_graph(UNNAMED_TEXT)
    assert module == "jit_step"
    scopes, inferred = profiler_mod.infer_scopes(graph)
    assert scopes.get(instruction, "") == scope
    assert inferred.get(instruction) == rule
    # the text's own view of it is as before: a name or nothing
    read = profiler_mod.instruction_scopes(UNNAMED_TEXT)[1]
    if rule is None and scope:
        assert read[instruction] == scope
    elif rule != "kin":
        assert instruction not in read


def test_the_graph_keeps_operands_calls_and_computations():
    graph = profiler_mod.instruction_graph(UNNAMED_TEXT)[1]
    assert graph["fusion.2"] == profiler_mod.Instruction(
        "", "fusion", ("p.1",), "fused_computation.2", "main.1")
    assert graph["mul.1"].computation == "fused_computation.2"
    assert graph["mul.1"].operands == ("add.0", "param_0")
    assert graph["copy-start.1"].opcode == "copy-start"  # a tuple's type
    kernel = graph["splash_mha_fwd_residuals.10"]
    assert kernel.opcode == "custom-call" and kernel.calls == ""
    assert kernel.operands == ("copy-done.1",)
    assert graph["ragged-dot-none.1"].operands == ("fusion.8", "p.2")
    assert graph["tuple.14"].operands == ("copy.13", "copy.9")


def test_a_walk_stops_at_its_depth_and_at_its_computation(monkeypatch):
    scopes = profiler_mod.infer_scopes(
        profiler_mod.instruction_graph(UNNAMED_TEXT)[1])[0]
    # the body's parameter is consumed by named instructions of the body
    assert scopes["param_0"] == f"{LM}/layer_0/mla_attn/rope/add"
    monkeypatch.setattr(profiler_mod, "WALK_DEPTH", 1)
    scopes, inferred = profiler_mod.infer_scopes(
        profiler_mod.instruction_graph(UNNAMED_TEXT)[1])
    # the kernel is two steps from the move's start: out of reach, so that
    # takes what made its operand (the fusion, by its body)
    assert inferred["copy-start.1"] == "operand"
    assert scopes["copy-start.1"] == f"{LM}/layer_0/mla_attn/rope/mul"
    assert inferred["copy-done.1"] == "user"


@pytest.mark.parametrize("steps, found", [(6, "user"), (9, "user"),
                                          (12, "user"), (13, None)])
def test_a_prefetch_sliced_twice_finds_its_reader_by_the_far_walk(
        steps, found):
    """A conditional's argument moved, sliced, laid out anew and sliced
    again before a named fusion reads it (nine unnamed steps in the third
    decoder's expert branches): nothing made it that has a name, the short
    walk ends after six, the far one after twelve."""
    chain = ["  %p.1 = f32[8]{0} parameter(0)"]
    for i in range(steps):  # copy.2 is ``steps`` steps from the fusion
        chain.append(f"  %copy.{i + 2} = f32[8]{{0}} copy(%{chain_name(i)})")
    chain.append(
        f"  ROOT %fusion.99 = f32[8]{{0}} fusion(%{chain_name(steps)}), "
        f'kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{LM}'
        '/layer_1/moe/dispatch/gather"}')
    text = UNNAMED_TEXT[:UNNAMED_TEXT.index("ENTRY")] \
        + "ENTRY %main.1 (p.1: f32[8]) -> f32[8] {\n" \
        + "\n".join(chain) + "\n}\n"
    scopes, inferred = profiler_mod.infer_scopes(
        profiler_mod.instruction_graph(text)[1])
    assert inferred.get("copy.2") == found
    assert scopes.get("copy.2", "") == (
        f"{LM}/layer_1/moe/dispatch/gather" if found else "")
    assert inferred[f"copy.{steps + 1}"] == "user"  # one step from it


def chain_name(i):
    return "p.1" if i == 0 else f"copy.{i + 1}"


# A trace of that step, as the TPU writes one: the key split's program, then
# the step's, with the loop thread's spans on a host clock that lags the
# device's by a millisecond. Own device microseconds of the step: fusion.2
# 100, copy-start.1 10, copy-done.1 40, the kernel 300, copy.7 50, the two
# ragged products 100 each, fusion.11 250 (read), copy.9 50: 1,000. The
# key split's one operation is named fusion.2 as well.
UNNAMED_TRACE = """
planes { name: "/device:TPU:0" id: 1
  lines { id: 1 name: "XLA Modules" timestamp_ns: 2000000
    events { metadata_id: 20 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 21 offset_ps: 100000000 duration_ps: 1000000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 2000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 100000000 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 200000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 210000000 duration_ps: 40000000 }
    events { metadata_id: 4 offset_ps: 250000000 duration_ps: 300000000 }
    events { metadata_id: 5 offset_ps: 550000000 duration_ps: 50000000 }
    events { metadata_id: 6 offset_ps: 600000000 duration_ps: 100000000 }
    events { metadata_id: 7 offset_ps: 700000000 duration_ps: 250000000 }
    events { metadata_id: 8 offset_ps: 950000000 duration_ps: 100000000 }
    events { metadata_id: 9 offset_ps: 1050000000 duration_ps: 50000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.2 = f32[8]{0:T(1024)} fusion(f32[8]{0} %p.1)" } }
  event_metadata { key: 2 value { id: 2 name: "%copy-start.1 = (f32[8]{0:T(1024)S(1)}, f32[8]{0:T(1024)}, u32[]{:S(2)}) copy-start(%fusion.2)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy-done.1 = f32[8]{0:T(1024)S(1)} copy-done(%copy-start.1)" } }
  event_metadata { key: 4 value { id: 4 name: "%splash_mha_fwd_residuals.10 = (f32[8]{0}, f32[8]{0}) custom-call(%copy-done.1)" } }
  event_metadata { key: 5 value { id: 5 name: "%copy.7 = f32[8]{0:T(256)} copy(%bitcast.6)" } }
  event_metadata { key: 6 value { id: 6 name: "%ragged-dot-none.1 = f32[8]{0} custom-call(%fusion.8, %p.2)" } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.11 = f32[8]{0} fusion(%ragged-dot-none.1)" } }
  event_metadata { key: 8 value { id: 8 name: "%ragged-dot-none.2 = f32[8]{0} custom-call(%fusion.11, %p.2)" } }
  event_metadata { key: 9 value { id: 9 name: "%copy.9 = f32[8]{0} copy(%p.2)" } }
  event_metadata { key: 20 value { id: 20 name: "jit__threefry_split(11)" } }
  event_metadata { key: 21 value { id: 21 name: "jit_step(12)" } }
}
planes { name: "/host:CPU" id: 2
  lines { id: 1 name: "python3" timestamp_ns: 2900000
    events { metadata_id: 1 offset_ps: 80000000 duration_ps: 15000000 }
    events { metadata_id: 2 offset_ps: 120000000 duration_ps: 40000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1500000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "train.rng" } }
  event_metadata { key: 2 value { id: 2 name: "train.step" } }
  event_metadata { key: 3 value { id: 3 name: "train" } }
}
"""


@pytest.fixture
def unnamed_trace(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        UNNAMED_TRACE))
    return str(path)


def test_inferred_scopes_reach_the_record_and_the_log_line(unnamed_trace,
                                                           monkeypatch):
    spans = {"train", "train.rng", "train.step"}
    module, read = profiler_mod.instruction_scopes(UNNAMED_TEXT)
    before = profiler_mod.summarize(
        profiler_mod.read_record(unnamed_trace, spans, module, read))
    # all but the kernel and the experts' multiply: 450 of the step's 1,000
    # (XLA's own name for the ragged products is no scope)
    assert before["block_ms/unscoped"] == 0.45
    assert before["inferred_pct"] == 0.0 and before["steps"] == 1
    scopes, inferred = profiler_mod.infer_scopes(
        profiler_mod.instruction_graph(UNNAMED_TEXT)[1])
    record = profiler_mod.read_record(unnamed_trace, spans, module, scopes,
                                      inferred)
    assert record["inferred"] == inferred
    plane = scope_reduce.first_plane(record)
    assert [(name, scope) for name, scope, _, _ in plane["ops"]][:3] == [
        ("fusion.2", "jit(_threefry_split)"),  # the other program's, as ever
        ("fusion.2", f"{LM}/layer_0/mla_attn/rope/mul"),
        ("copy-start.1", f"{LM}/layer_0/mla_attn/core/pallas_call")]
    assert set(plane) == {"ops", "modules"}
    after = profiler_mod.summarize(record)
    assert after["block_ms/unscoped"] == 0.05  # copy.9: nothing reaches it
    assert after["block_ms/fwd/mla_attn"] == 0.45  # the kernel's 300 before
    assert after["block_ms/fwd/moe"] == 0.5
    # of 1,010 us of own time (the key split's 10 are not the step's)
    assert after["inferred_pct"] == round(100 * 400 / 1010, 3)
    assert after["step_device_ms"] == before["step_device_ms"] == 1.0
    # the benchmark's readers, from the same record: the ragged products are
    # found once, by name and by scope; the new reader agrees with the line
    from benchmark.readers import (
        inferred_scope_device_pct, scope_paths_device_ms,
        unscoped_model_device_pct,
    )

    run = {"trace": {"planes": {"/device:TPU:0": [
        (o[0], o[2], o[3]) for o in plane["ops"]]}}}
    monkeypatch.setattr(scope_reduce, "program_record", lambda: record)
    assert inferred_scope_device_pct.read(run, {}) == pytest.approx(
        100 * 400 / 1010)
    assert unscoped_model_device_pct.read(run, {"model": "M"}) == \
        pytest.approx(100 * 50 / 1010)
    experts = {"scopes": ["moe/experts"], "instructions": ["ragged-dot"]}
    assert scope_paths_device_ms.read(run, experts) == pytest.approx(0.45)
    assert scope_paths_device_ms.read(
        run, {"scopes": ["moe/router", "moe/dispatch", "moe/combine"]}
    ) == pytest.approx(0.05)  # copy.7, and no ragged product
    assert scope_paths_device_ms.read(
        run, {"scopes": ["mla_attn"]}) == pytest.approx(0.45)
    assert scope_paths_device_ms.read(
        run, {"all_but": ["mla_attn", "moe"],
              "instructions": ["ragged-dot"]}) == pytest.approx(0.06)


def test_host_spans_are_moved_onto_the_devices_clock(unnamed_trace):
    """The host's clock lags by a millisecond in the hand-made trace: as
    stamped, the key split runs 980 us before ``train.rng`` begins and the
    step 920 us before ``train.step`` does. The smallest shift that mends
    both puts ``train.rng`` at the split's start, and the 90 us between the
    two programs then fall to the spans that cover them."""
    spans = {"train", "train.rng", "train.step"}
    record = profiler_mod.read_record(unnamed_trace, spans, "jit_step", {})
    assert record["clock_offset_ns"] == -980_000
    assert [(h[0], h[1], h[2]) for h in record["host"]] == [
        ("train", 1_920_000, 3_420_000), ("train.rng", 2_000_000, 2_015_000),
        ("train.step", 2_040_000, 2_080_000)]
    line = profiler_mod.summarize(record)
    assert line["clock_offset_ns"] == -980_000
    assert line["idle_ms/train.rng"] == 0.005
    assert line["idle_ms/train.step"] == 0.04
    assert line["idle_ms/outside_any_span"] == 0.045


@pytest.mark.parametrize("host_lag_ns, offset", [
    (0, 0), (-500_000, 0),  # a host clock that is right, or early: causal
    (15_000, 0),  # late by less than the dispatch takes
    (1_000_000, -980_000),
])
def test_clock_offset_is_the_smallest_shift_that_mends_causality(
        host_lag_ns, offset):
    modules = [("jit_step(1)", 1_000_000, 1_900_000),  # sent before the trace
               ("jit__threefry_split(2)", 2_000_000, 2_010_000),
               ("jit_other(4)", 2_050_000, 2_060_000),
               ("jit_step(1)", 2_100_000, 3_100_000)]
    host = [("train", 1_900_000, 3_400_000, "python3", {}),
            ("train.rng", 1_980_000, 1_995_000, "python3", {}),
            ("train.step", 2_020_000, 2_060_000, "python3", {}),
            ("train.step", 0, 1, "another-thread", {})]
    host = [(n, a + host_lag_ns, b + host_lag_ns, t, args)
            for n, a, b, t, args in host]
    assert profiler_mod.clock_offset(modules, host, "jit_step") == offset
    assert profiler_mod.clock_offset([], host, "jit_step") == 0  # CPU


# ------------------------------------- (b) spans on the profiler's clock ---


def host_events(trace_dir, names) -> list:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(paths) == 1
    return profiler_mod.read_record(paths[0], names)["host"]


def test_enabled_tracer_span_lands_in_the_profilers_host_plane(tmp_path):
    tracer = Tracer(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in (3, 4):
            with tracer.step("train", i):
                with tracer.span("train.step", step=i, compiles=7):
                    jnp.ones((4,)).block_until_ready()
        tracer.instant("train.mark", step=4)
    finally:
        jax.profiler.stop_trace()
    assert tracer.annotated_names() == {"train", "train.step", "train.mark"}
    host = host_events(str(tmp_path), tracer.annotated_names())
    spans = [h for h in host if h[0] == "train.step"]
    assert [h[4]["step"] for h in spans] == [3, 4]
    assert spans[0][4]["compiles"] == 7
    steps = [h for h in host if h[0] == "train"]
    assert [h[4]["step_num"] for h in steps] == [3, 4]
    # the span lies inside its step's annotation, on one clock and thread
    assert steps[0][1] <= spans[0][1] <= spans[0][2] <= steps[0][2]
    assert spans[0][3] == steps[0][3]
    # and the tracer's own events are as before
    assert [e["args"]["step"] for e in tracer.events()
            if e["name"] == "train.step"] == [3, 4]


def test_disabled_tracer_builds_no_annotation_and_no_listener(monkeypatch):
    built = []
    real = jax.profiler.TraceAnnotation

    def counting(*args, **kwargs):
        built.append(args)
        return real(*args, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", counting)
    monkeypatch.setattr(tracing, "_listen_for_compiles",
                        lambda: built.append("listener"))
    tracer = Tracer(enabled=False)
    with tracer.step("train", 0):
        with tracer.span("train.step", step=0) as sp:
            sp.set(x=1)
    tracer.instant("train.mark")
    assert built == [] and tracer.annotated_names() == set()
    on = Tracer(enabled=True)
    with on.span("train.step", step=0):
        pass
    assert built == ["listener", ("train.step",)]


def test_tracer_keeps_the_newest_events_when_bounded():
    tracer = Tracer(enabled=True, max_events=3)
    for i in range(5):
        tracer.instant("mark", i=i)
    assert [e["args"]["i"] for e in tracer.events()] == [2, 3, 4]


# -------------------------------------------------- (e) compile counter ---


def test_compile_counter_rises_once_per_new_shape():
    Tracer(enabled=True)  # registers the listeners, once per process

    @jax.jit
    def fresh(x):
        return x * 2.0 + 1.0

    a3 = jax.device_put(np.ones((3,), np.float32))
    a5 = jax.device_put(np.ones((5,), np.float32))
    before = compile_counts()
    fresh(a3).block_until_ready()
    first = compile_counts()
    fresh(a3).block_until_ready()
    repeat = compile_counts()
    fresh(a5).block_until_ready()
    second = compile_counts()
    assert first["compiles"] == before["compiles"] + 1
    assert repeat["compiles"] == first["compiles"]
    assert second["compiles"] == first["compiles"] + 1
    assert first["trace_s"] > before["trace_s"]
    assert first["backend_s"] > before["backend_s"]
    assert second["cache_hits"] <= second["compiles"]


# ----------------------------------- (c) the reduction, on a written record


def us(x):
    return 1000.0 * x


def written_record() -> dict:
    """The benchmark's hand-written record (one execution of the step after
    an RNG program, a ``while`` holding two kernels, a gap of 90 us between
    the programs), its microseconds as nanoseconds."""
    path = os.path.join(os.path.dirname(scope_reduce.__file__), "..", "tests",
                        "data", "small_record.json")
    with open(path) as f:
        record = json.load(f)
    for plane in record["devices"].values():
        for key in ("ops", "modules", "steps"):
            plane[key] = [(*row[:-2], us(row[-2]), us(row[-1]))
                          for row in plane[key]]
    record["host"] = [(n, us(a), us(b), t, args)
                      for n, a, b, t, args in record["host"]]
    return record


def planes_of(record) -> dict:
    """What ``harness/train.py`` keeps of the same trace."""
    return {name: [(o[0], o[2], o[3]) for o in plane["ops"]]
            for name, plane in record["devices"].items()}


def test_own_time_by_block_forward_and_backward_apart():
    plane = scope_reduce.first_plane(written_record())
    sums = scope_reduce.by_block(plane)
    assert sums["blocks"] == {
        "jit(_threefry_split)": us(10), "fwd/trunk": us(100),
        "fwd/pair_from_msa": us(300), "fwd/pair_axial": us(200),
        "bwd/pair_from_msa": us(200), "bwd/msa_ff": us(50),
        "fwd/loss": us(20), "grad_clip": us(10), "optimizer": us(20),
        "unscoped": us(50), "bwd/pair_axial": us(50),
    }
    assert sums["groups"] == {
        "cross_attn": us(500), "pair_axial": us(250), "msa_axial": 0.0,
        "feedforward": us(50), "model_rest": us(100),
        "outside_model": us(110),
    }
    assert sums["unnamed"] == us(50) and sums["total"] == us(1010)
    assert scope_reduce.step_runs(plane, "jit_step") == [(us(100), us(1100))]
    assert scope_reduce.collective_by_block(plane, ["all-reduce"]) == {
        "bwd/pair_axial": us(50)}
    assert scope_reduce.collective_by_block(plane, ["all-gather"]) == {}


def test_the_programs_own_line_splits_a_ring_into_kernels_and_merging():
    record = written_record()
    plane = next(iter(record["devices"].values()))
    cross = "jit(step)/transpose(jvp(Alphafold2))/trunk/layer_0/pair_from_msa"
    end = max(o[3] for o in plane["ops"])
    plane["ops"] = list(plane["ops"]) + [
        ("flash_mha_bwd_dq.1", f"{cross}/shard_map/ring_block/"
         "flash_mha_bwd_dq_block_q_major=1024/pallas_call", end, end + us(70)),
        ("add_fusion.7", f"{cross}/shard_map/ring_merge/add",
         end + us(70), end + us(80)),
        ("collective-permute-done.3", f"{cross}/shard_map/ring_merge/ppermute",
         end + us(80), end + us(85)),
    ]
    line = profiler_mod.summarize(record)
    assert line["ring_ms/ring_block"] == 0.07
    assert line["ring_ms/ring_merge"] == 0.015
    assert line["block_ms/bwd/pair_from_msa"] == 0.285  # 0.2 before
    assert line["collective_own_ms/bwd/pair_from_msa"] == 0.005
    # no ring in the program, no such keys in its line
    assert not [k for k in profiler_mod.summarize(written_record())
                if k.startswith("ring_ms")]


def test_gaps_go_to_the_innermost_covering_span():
    record = written_record()
    plane = scope_reduce.first_plane(record)
    gaps = scope_reduce.idle_gaps(plane["ops"], us(20))
    assert gaps == [(us(10), us(100))]
    assert scope_reduce.idle_by_span(gaps, record["host"]) == {
        "train.rng": us(20), "train.step": us(30), "train.dispatch": us(10),
        "outside_any_span": us(30),
    }
    assert scope_reduce.idle_gaps(plane["ops"], us(100)) == []


def test_the_programs_own_line_agrees_with_the_readers_arithmetic():
    line = profiler_mod.summarize(written_record())
    assert line["steps"] == 1 and line["step_device_ms"] == 1.0
    assert line["block_ms/fwd/pair_from_msa"] == 0.3
    assert line["block_ms/bwd/pair_from_msa"] == 0.2
    assert line["block_ms/fwd/trunk"] == 0.1
    assert line["block_ms/unscoped"] == 0.05
    assert line["collective_own_ms/bwd/pair_axial"] == 0.05
    assert line["idle_ms/train.dispatch"] == 0.01
    assert line["idle_ms/outside_any_span"] == 0.03
    assert line["idle_pct"] == round(100 * 90 / 1100, 3)


def test_block_reader_checks_the_record_against_the_harness(monkeypatch):
    from benchmark.readers import scope_device_ms

    record = written_record()
    monkeypatch.setattr(scope_reduce, "program_record", lambda: record)
    run = {"trace": {"planes": planes_of(record)}}
    assert scope_device_ms.read(run, {"group": "cross_attn"}) == 0.5
    assert sum(scope_device_ms.read(run, {"group": g})
               for g in scope_reduce.ALL_GROUPS) == pytest.approx(1.01)
    events = next(iter(run["trace"]["planes"].values()))
    events.remove(("ff.1", us(900), us(950)))  # 5% of the trace is missing
    with pytest.raises(RuntimeError, match="not the same trace"):
        scope_device_ms.read(run, {"group": "cross_attn"})


# ------------------------ (d) the record after train() was left early ---


class Stop(Exception):
    pass


def test_record_and_spans_survive_an_exception_from_a_callback(
        tmp_path, monkeypatch, capsys):
    from alphafold2_tpu.train.loop import train

    closed = []
    real_close = Tracer.close
    monkeypatch.delattr(Tracer, "__del__")  # it would close a second time
    monkeypatch.setattr(
        Tracer, "close", lambda self: (closed.append(1), real_close(self)))

    def leave_at_3(i, state, metrics):
        if i == 3:
            raise Stop

    cfg = tiny_config(depth=1, features="none",
                      profile_dir=str(tmp_path / "trace"),
                      profile_steps=(1, 3))
    with pytest.raises(Stop):
        train(cfg, callbacks=[leave_at_3])
    assert closed == [1]
    record = profiler_mod.last_record()
    plane = scope_reduce.first_plane(record)
    assert plane is not None and record["step_module"] == "jit_step"
    assert any(scope.startswith("jit(step)/") for _, scope, _, _ in
               plane["ops"])
    traced = {h[4].get("step") for h in record["host"]
              if h[0] == "train.step"}
    assert traced == {1, 2, 3}
    assert {h[4]["step_num"] for h in record["host"] if h[0] == "train"} \
        == {2}  # whole iterations inside the trace
    names = {e["name"] for e in record["spans"]}
    assert {"train.imports", "train.dataset", "train.build_model",
            "train.first_batch", "train.init_state", "train.lower",
            "train.compile", "train.rng",
            "train.step", "train.profiler", "train.log", "train.callbacks",
            "train.next_batch", "train.triage_wait"} <= names
    assert "train.cost_analysis" not in names  # XLA's count: nothing read it
    assert "inferred" in record and record["clock_offset_ns"] == 0  # CPU
    compile_span = scope_reduce.span_events(record, "train.compile")[0]
    assert set(compile_span["args"]) == {"backend_s", "cache_hit"}
    assert set(scope_reduce.span_events(record, "train.lower")[0]["args"]) \
        == {"trace_s", "lower_s"}
    left = scope_reduce.span_events(record, "train.callbacks")[-1]
    assert left["args"] == {"step": 3, "error": "Stop"}
    assert record["compile_counts"]["compiles"] >= 1
    # the one line: device time by block, idle time by host span
    lines = [line for line in capsys.readouterr().out.splitlines()
             if "event=profile" in line]
    assert len(lines) == 1
    assert "block_ms/fwd/" in lines[0] and "block_ms/bwd/" in lines[0]
    assert "idle_ms/" in lines[0]
    # and the next trace can start: none was left running
    jax.profiler.start_trace(str(tmp_path / "again"))
    jax.profiler.stop_trace()


def test_train_hands_the_loop_a_null_tracer_unless_asked(
        tmp_path, monkeypatch):
    """With neither ``profile_dir`` nor ``trace_events`` the loop gets the
    disabled tracer (which builds no annotation and registers no listener,
    see above) and a profiler that reads nothing; ``profile_dir`` alone turns
    both on, spans in memory."""
    from alphafold2_tpu.train import loop

    seen = []
    monkeypatch.setattr(
        loop, "_train",
        lambda cfg, n, data, cbs, tracer, logger, profiler, init_params:
        seen.append((tracer.enabled, profiler.enabled)))
    monkeypatch.setattr(profiler_mod, "_LAST", None)
    loop.train(tiny_config())
    assert seen == [(False, False)] and profiler_mod.last_record() is None
    loop.train(tiny_config(profile_dir=str(tmp_path)))
    assert seen[-1] == (True, True)
    assert profiler_mod.last_record()["spans"] == []


def test_an_untraced_run_reads_no_text_and_builds_no_graph(monkeypatch):
    """Without ``profile_dir`` the loop never asks for the compiled step's
    text on the profiler's behalf: nothing is parsed, no graph is built."""
    from alphafold2_tpu.train import loop

    reached = []
    for name in ("instruction_graph", "infer_scopes"):
        monkeypatch.setattr(
            profiler_mod, name,
            lambda *args, name=name: reached.append(name) or ("", {}))
    monkeypatch.setattr(
        profiler_mod.Profiler, "name_operations",
        lambda self, text: reached.append("name_operations"))
    monkeypatch.setattr(profiler_mod, "_LAST", None)
    cfg = tiny_config(depth=1, features="none")
    cfg.train.num_steps = 2
    loop.train(cfg)
    assert reached == [] and profiler_mod.last_record() is None

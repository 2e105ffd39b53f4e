"""``tools/compile_cell_program.py`` reads a compiled program's text:
which fusions hold a gather, at what width, under which stage, and
whether the compiler placed each operand in memory space ``S(1)``. The
reader is held to a few lines of real v5e text (the star cell's Q3.1
program, PR 38); the compile itself needs the TPU compiler and two
minutes, and is run by hand."""

import importlib.util
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TEXT = '''
%fused_computation.1 (param_0.5: s32[524288], param_1.9: s32[67108864]) -> s32[67108864] {
  %param_0.5 = s32[524288]{0:T(1024)S(1)} parameter(0)
  %param_1.9 = s32[67108864]{0:T(1024)} parameter(1)
  %gather.5 = s32[67108864]{0:T(1024)} gather(%param_0.5, %param_1.9), offset_dims={}
  ROOT %reshape.14 = s32[67108864]{0:T(1024)} reshape(%gather.5)
}

%fused_computation.6 (param_0.9: s64[67108864]) -> s32[67108864] {
  %param_0.9 = s64[67108864]{0:T(1024)} parameter(0)
  ROOT %clamp.2 = s32[67108864]{0:T(1024)} clamp(%param_0.9)
}

ENTRY %main.13 (pd.1: s64[67108864]) -> s32[67108864] {
  %pd.1 = s64[67108864]{0:T(1024)} parameter(0)
  %copy-done.7 = s32[524288]{0:T(1024)} copy-done(%copy-start.7)
  %fusion.3 = s32[524288]{0:T(1024)S(1)} fusion(%copy-done.7), kind=kCustom, calls=%fused_computation.9
  %convert_clamp_fusion = s32[67108864]{0:T(1024)} fusion(%pd.1), kind=kLoop, calls=%fused_computation.6, metadata={op_name="jit(program_dag_grouped)/shard_map/otb/join2/fold/probe/jit(_take)/gather"}
  %fusion.4 = s32[67108864]{0:T(1024)} fusion(%fusion.3, %convert_clamp_fusion), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(program_dag_grouped)/shard_map/otb/join2/fold/probe/jit(_take)/gather" stack_frame_id=28}
  ROOT %fusion.9 = s32[67108864]{0:T(1024)} fusion(%copy-done.7, %convert_clamp_fusion), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(program_dag_grouped)/shard_map/otb/join4/fold/gather/jit(_take)/gather"}
}
'''


def test_gather_fusions_reads_stage_width_and_memory_space():
    spec = importlib.util.spec_from_file_location(
        "compile_cell_program",
        os.path.join(ROOT, "tools", "compile_cell_program.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)  # (imports no JAX)
    found = tool.gather_fusions(TEXT, 10_000)
    assert [(op, stage) for op, _res, stage, _ops in found] == [
        ("%fusion.4", "join2/fold/probe"), ("%fusion.9", "join4/fold/gather")]
    (_, res, _, (table, index)), (_, _, _, (table9, _)) = found
    assert res.startswith("s32[67108864]")
    assert table[0] == "%fusion.3" and "S(1)" in table[1]
    assert table[2] == "fusion" and index[0] == "%convert_clamp_fusion"
    # a table the compiler left in HBM reads without the annotation
    assert table9[0] == "%copy-done.7" and "S(1)" not in table9[1]
    assert table9[2] == "copy-done"
    assert tool.gather_fusions(TEXT, 100_000_000) == []

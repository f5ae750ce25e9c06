"""`rehearse_text.without_locations`: what it takes out of a compiled
program's text (the tables of source locations, each instruction's
metadata, a kernel body's debug locations) and what it leaves."""

import base64
import io

from benchmark import rehearse_text


def kernel_body(line):
    """A module as a Pallas kernel's `backend_config` carries it:
    serialized MLIR with the location of the Python that traced it."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    with jax_mlir.make_ir_context() as context:
        context.allow_unregistered_dialects = True
        module = ir.Module.parse(
            'module { "test.op"() : () -> () loc("model.py":%d:1) }' % line)
        out = io.BytesIO()
        module.operation.write_bytecode(out)
    return base64.b64encode(out.getvalue()).decode()


def program(line, width=8):
    return "\n".join([
        "HloModule jit_step, is_scheduled=true",
        "", "FileNames", '1 "model.py"', "", "FunctionNames", '1 "f"', "",
        "FileLocations", "1 {file_name_id=1 function_name_id=1 line=%d}"
        % line, "", "StackFrames", "1 {file_location_id=1}", "", "",
        "ENTRY %%main (p: f32[%d]) -> f32[%d] {" % (width, width),
        '  %%p = f32[%d]{0} parameter(0), metadata={op_name="p" '
        'source_file="model.py" source_line=%d}' % (width, line),
        '  %%k = f32[%d]{0} custom-call(%%p), custom_call_target='
        '"tpu_custom_call", backend_config={"custom_call_config":{"body":'
        '"%s"}}, metadata={op_name="jit(step)/k" stack_frame_id=1}'
        % (width, kernel_body(line)),
        "}"])


def test_a_moved_line_of_python_is_no_other_program():
    a, kernels = rehearse_text.without_locations(program(10))
    b, _ = rehearse_text.without_locations(program(99))
    assert a == b and kernels == 1
    assert "metadata" not in a and "StackFrames" not in a
    assert "model.py" not in a
    assert a.startswith("HloModule jit_step") and "test.op" in a
    assert 'custom_call_target="tpu_custom_call"' in a


def test_another_shape_is_another_program():
    a, _ = rehearse_text.without_locations(program(10))
    b, _ = rehearse_text.without_locations(program(10, width=16))
    assert a != b

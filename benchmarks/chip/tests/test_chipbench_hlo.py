"""Kernel calls read out of compiled HLO text."""
import hlo

# Lines of programs compiled for a v5e (shortened after the layouts).
LINE = (
    '  %syr2k_lower.1 = f32[512,512]{1,0:T(8,128)S(1)} custom-call(%copy-done.2, '
    '%copy-done.1, %copy-done, %copy-done, %copy-done, /*index=5*/%copy-done, '
    '%broadcast_multiply_fusion), custom_call_target="tpu_custom_call", '
    'operand_layout_constraints={s32[3]{0}, s32[3]{0}, f32[512,256]{1,0}, '
    'f32[512,256]{1,0}, f32[512,256]{1,0}, f32[512,256]{1,0}, f32[512,512]{1,0}}, '
    'frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(<lambda>)/'
    'syr2k_lower/pallas_call" stack_frame_id=6}'
)
TUPLE = (
    '  %fused_panel_update.3 = (f32[32,768,768]{2,1,0:T(8,128)}, f32[32,1024,256]'
    '{2,1,0:T(8,128)}, f32[32,1024,256]{2,1,0:T(8,128)S(1)}, f32[32,32,8,8]'
    '{3,2,1,0:T(8,128)}) custom-call(%copy-done.102, %copy-done.103, %fusion.97), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[21]{0}, '
    's32[21]{0}, f32[32,1024,1024]{2,1,0}}, frontend_attributes={kernel_metadata={}}, '
    'metadata={op_name="jit(<lambda>)/jit(_inv_body)/vmap(jit(_inverse_pth_root))/'
    'jit(_execute)/jit(fused_panel_update_pallas)/fused_panel_update/pallas_call"}'
)


def test_kernels_in_and_shapes():
    text = "\n".join([LINE, TUPLE, "  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop"])
    assert hlo.kernels_in(text) == ["fused_panel_update", "syr2k_lower"]
    calls = hlo.custom_calls(text)
    s = calls["syr2k_lower.1"]
    assert s.kernel == "syr2k_lower"
    assert [x.dims for x in s.results] == [(512, 512)]
    assert [x.dims for x in s.operands] == [(3,), (3,)] + [(512, 256)] * 4 + [(512, 512)]
    assert s.operands[-1].itemsize == 4 and s.operands[0].dtype == "s32"
    f = calls["fused_panel_update.3"]
    assert [x.dims for x in f.results] == [
        (32, 768, 768), (32, 1024, 256), (32, 1024, 256), (32, 32, 8, 8)
    ]
    assert f.operands[-1].dims == (32, 1024, 1024)

"""GEMM and collective op selection from a compiled program's HLO text."""
import jax
import jax.numpy as jnp

from bench import hlo_ops

SNIPPET = """HloModule jit_step

%fused_inner (p0: bf16[8,8], p1: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %p1 = bf16[8,8]{1,0} parameter(1)
  ROOT %convolution.1 = bf16[8,8]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf
}

%fused_outer (p0.1: bf16[8,8], p1.1: bf16[8,8]) -> bf16[8,8] {
  %p0.1 = bf16[8,8]{1,0} parameter(0)
  %p1.1 = bf16[8,8]{1,0} parameter(1)
  ROOT %fusion.9 = bf16[8,8]{1,0} fusion(%p0.1, %p1.1), kind=kOutput, calls=%fused_inner
}

%fused_add (a: f32[8], b: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %b = f32[8]{0} parameter(1)
  ROOT %add.3 = f32[8]{0} add(%a, %b)
}

%sum (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.4 = f32[] add(%x, %y)
}

ENTRY %main (w: bf16[8,8], v: f32[8]) -> (bf16[8,8], f32[8]) {
  %w = bf16[8,8]{1,0} parameter(0)
  %v = f32[8]{0} parameter(1)
  %convolution_add_fusion.7 = bf16[8,8]{1,0} fusion(%w, %w), kind=kOutput, calls=%fused_outer
  %add_fusion = f32[8]{0} fusion(%v, %v), kind=kLoop, calls=%fused_add
  %custom-call.2 = bf16[8,8]{1,0} custom-call(%w, %w), custom_call_target="tpu_custom_call"
  %all-reduce-start = (f32[8]{0}, f32[8]{0}) all-reduce-start(%add_fusion), replica_groups={{0,1}}, to_apply=%sum
  %all-reduce-done = f32[8]{0} all-reduce-done(%all-reduce-start)
  ROOT %tuple = (bf16[8,8]{1,0}, f32[8]{0}) tuple(%convolution_add_fusion.7, %all-reduce-done)
}
"""


def test_gemms_are_found_through_nested_fusions_and_pallas_calls():
    assert hlo_ops.select(SNIPPET, "gemm") == {"convolution_add_fusion.7",
                                               "custom-call.2"}


def test_collectives_are_found_by_opcode():
    assert hlo_ops.select(SNIPPET, "collective") == {"all-reduce-start",
                                                    "all-reduce-done"}


def test_selection_from_a_compiled_step():
    """Every dot of a small two-layer MLP's train step lands in the set,
    whatever the fusions are called."""
    def loss(ws, x):
        h = jax.nn.relu(x @ ws[0])
        return jnp.mean(h @ ws[1])

    ws = [jnp.ones((16, 16)), jnp.ones((16, 4))]
    text = jax.jit(jax.grad(loss)).lower(ws, jnp.ones((8, 16))).compile(
    ).as_text()
    gemms = hlo_ops.select(text, "gemm")
    comps = hlo_ops.parse(text)
    fused = {c for ins in comps.values() for _, op, line in ins
             if op == "fusion" for c in hlo_ops._called(line)}
    top_level_dots = [n for c, ins in comps.items() if c not in fused
                      for n, op, _ in ins if op == "dot"]
    assert gemms and set(top_level_dots) <= gemms
    assert not hlo_ops.select(text, "collective")

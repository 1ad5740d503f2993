"""The main path's Pallas kernels, compiled for a described v5e at real widths.

The TPU compiler is installed where the tests run; it compiles for a chip
that is described (``v5e:2x2``) and not attached.  That shows what interpret
mode cannot: a block Mosaic cannot tile, a slab over the VMEM limit.  Nothing
runs, so nothing here says a kernel is right or fast — ``chip_smoke.py`` and
the interpret-mode tests do that.

This is the only test file that describes a topology, and it does so inside a
fixture: the process that does it holds the TPU library's lock until it exits.
``tests/conftest.py`` has the persistent compile cache off, so these compiles
are neither written to it nor (unreadably, without a chip) looked up in it.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from autodist_tpu.models import FusedBatchNorm
from autodist_tpu.models.llama import LlamaConfig
from autodist_tpu.ops.pallas import flash_attention as F
from autodist_tpu.ops.pallas import fused_norm as N
from autodist_tpu.ops.pallas import gated_delta as G
from autodist_tpu.ops.pallas import grouped_matmul as M
from autodist_tpu.ops.pallas import quantize as Q

# GPT-2-small's training shape in chip_smoke.py: (B, S, H, D)
GPT_ATTN = (32, 1024, 12, 64)
# the attention shapes of the benchmark's GPT cells, a chip: gpt2_medium at
# batch 32, gpt2_large at global batch 16 over four chips
BENCHMARK_ATTN = {"gpt2_medium": (32, 1024, 16, 64),
                  "gpt2_large": (4, 1024, 20, 64)}
# every BatchNorm input of ResNet-50 at B=256, 224x224: (rows, channels)
RESNET50_B256_BN_SITES = [
    (256 * 112 * 112, 64), (256 * 56 * 56, 64), (256 * 56 * 56, 256),
    (256 * 56 * 56, 128), (256 * 28 * 28, 128), (256 * 28 * 28, 512),
    (256 * 28 * 28, 256), (256 * 14 * 14, 256), (256 * 14 * 14, 1024),
    (256 * 14 * 14, 512), (256 * 7 * 7, 512), (256 * 7 * 7, 2048)]
# the same per sample, for GroupNorm: (rows per sample, channels)
RESNET50_GN_SITES = [(r // 256, c) for r, c in RESNET50_B256_BN_SITES]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or its lock is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _aval(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# tests/conftest.py turns the backend's optimisations off for the CPU's sake;
# the compiler for the chip runs as it does on the chip
TPU_DEFAULTS = {"xla_backend_optimization_level": 3,
                "xla_llvm_disable_expensive_passes": False}


def _compile(fn, *avals):
    text = jax.jit(fn).lower(*avals).compile(
        compiler_options=TPU_DEFAULTS).as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the executable"
    return text


# ------------------------------------------------------- flash attention --

@pytest.mark.parametrize("kv_heads", [
    GPT_ATTN[2], LlamaConfig().num_kv_heads], ids=["mha", "gqa"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_flash_attention_gpt_small_train_shape(one_chip, kv_heads, grad):
    b, s, h, d = GPT_ATTN
    q = _aval(one_chip, (b, s, h, d), jnp.bfloat16)
    kv = _aval(one_chip, (b, s, kv_heads, d), jnp.bfloat16)

    def loss(q, k, v):
        out = F.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1, 2)) if grad else loss, q, kv, kv)


def _kernel_shapes(text):
    """The result shapes of each ``tpu_custom_call`` of a compiled text."""
    out = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            result = line.split(" = ", 1)[1].split(" custom-call(", 1)[0]
            out.append([p.split("{")[0]
                        for p in result.strip("()").split("}, ")])
    return out


def _kernel_results(text):
    """The result types of each ``tpu_custom_call`` of a compiled text, split
    as ``benchmark/layer_metrics/flash_attn_roofline.py`` splits them."""
    return sorted([p.split("[")[0] for p in call]
                  for call in _kernel_shapes(text))


@pytest.mark.parametrize("config", sorted(BENCHMARK_ATTN))
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_flash_attention_benchmark_shapes_and_signatures(one_chip, config,
                                                         grad):
    """The three kernels compile at the shapes the benchmark's GPT cells run,
    with the result signatures its roofline reader tells them apart by:
    forward ``(out, f32 row statistics)``, dq one result, dk/dv two results
    in the input dtype."""
    qkv = _aval(one_chip, BENCHMARK_ATTN[config], jnp.bfloat16)

    def loss(q, k, v):
        out = F.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)) if grad else loss,
                    qkv, qkv, qkv)
    want = [["bf16", "f32"]]
    if grad:
        want += [["bf16"], ["bf16", "bf16"]]
    assert _kernel_results(text) == sorted(want)


_BYTES = {"bf16": 2, "f32": 4}
_SHAPE = re.compile(r"\b(bf16|f32)\[([0-9,]+)\]")


def test_gpt2_medium_block_lays_nothing_out_anew_round_the_kernels(
        one_chip, monkeypatch):
    """One ``nn.remat`` ``GPTBlock`` of the benchmark's GPT cell, forward,
    recomputation and backward: the kernels take the ``qkv`` projection's
    result as it is and hand ``out``, dq, dk and dv over in the projections'
    layout, so no ``copy`` under ``attn/`` moves a whole activation (the
    folded layout had 18 of 64 MB each, PERF.md PR 31), and no kernel
    operand or result has a minor dimension of one head's 64 lanes."""
    import flax.linen as nn

    from autodist_tpu.models import gpt

    b, s, h, d = BENCHMARK_ATTN["gpt2_medium"]
    monkeypatch.setattr(F, "_on_tpu", lambda: True)     # compiled kernels
    cfg = gpt.GPTConfig(vocab_size=512, hidden_size=h * d, num_layers=1,
                        num_heads=h, intermediate_size=4 * h * d,
                        max_position=s, dtype=jnp.bfloat16)
    block = nn.remat(gpt.GPTBlock, static_argnums=(2,))(cfg, name="h_0")
    params = jax.eval_shape(lambda: block.init(
        jax.random.key(0), jnp.zeros((1, 8, h * d), jnp.bfloat16), True))
    params = jax.tree.map(lambda a: _aval(one_chip, a.shape, a.dtype), params)

    def loss(p, x):
        return jnp.sum(block.apply(p, x, True).astype(jnp.float32))

    text = _compile(jax.value_and_grad(loss), params,
                    _aval(one_chip, (b, s, h * d), jnp.bfloat16))
    kernels = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line]
    assert len(kernels) == 4        # forward, recomputed forward, dq, dk/dv
    for line in kernels:
        assert "/attn/" in line
        for _, dims in _SHAPE.findall(line.split(", metadata=")[0]):
            assert int(dims.split(",")[-1]) != d, line[:200]
    whole = b * s * h * d * 2
    for line in text.splitlines():
        m = re.search(r" = (\S+) copy\(", line)
        if m and "/attn/" in line:
            dtype, dims = _SHAPE.search(m.group(1)).groups()
            size = _BYTES[dtype] * int(np.prod([int(x) for x in
                                               dims.split(",")]))
            assert size < whole, line[:300]


# the one softmax-attention layer of the benchmark's Qwen3-Next cell:
# (B, S, query heads, head size) on QWEN3_NEXT_KV_HEADS K/V heads
QWEN3_NEXT_ATTN = (4, 8192, 16, 256)
QWEN3_NEXT_KV_HEADS = 2


def test_flash_attention_qwen3_next_shape_and_signatures(one_chip):
    """D = 256, eight query heads to a K/V head, S = 8,192: a head is two
    whole lane blocks of the projections' (B, S, H*D) layout; one head's K
    and V rows, double-buffered, pass the 14 MiB budget, so by their own
    rule the kernels take one head a program under the raised limit and walk
    the k tiles in a loop (sixteen tiles are too many static cases).  The
    result signatures are what
    ``benchmark/layer_metrics/full_attn_roofline.py`` tells the kernels apart
    by: forward two results of different shapes, dq one, dk/dv two of one
    shape (float32, one per query head)."""
    b, s, h, d = QWEN3_NEXT_ATTN
    group = h // QWEN3_NEXT_KV_HEADS
    heads = F._Heads(d, F._pack(h, group, d, 128))
    assert heads.pack == 1
    assert F._pick_heads(heads, F._together(heads, b * h, h, group, False),
                         s, s, 2, 512, 512) == 1
    assert not F._prefix(True, s, s, 512, s)
    assert F._VMEM_BUDGET < F._vmem_bytes(heads, 1, s, s, 2, 512, 512) \
        <= F._VMEM_LIMIT * 3 // 4
    q = _aval(one_chip, (b, s, h, d), jnp.bfloat16)
    kv = _aval(one_chip, (b, s, QWEN3_NEXT_KV_HEADS, d), jnp.bfloat16)

    def loss(q, k, v):
        out = F.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    want = [[f"bf16[{b},{s},{h * d}]", f"f32[{b * h},1,{s}]"],
            [f"bf16[{b},{s},{h * d}]"],
            [f"f32[{b},{s},{h * d}]", f"f32[{b},{s},{h * d}]"]]
    assert sorted(_kernel_shapes(text)) == sorted(want)


# the delta rule of the benchmark's Qwen3-Next cell: (B, S, key heads, value
# heads, head size), chunks of 64
QWEN3_NEXT_RULE = (4, 8192, 16, 32, 128)


def test_gated_delta_rule_qwen3_next_shape_and_signatures(one_chip):
    """The rule's two kernels at the cell's shape, bfloat16, inside the VMEM
    an operation may scope by default (no limit is passed: a kernel that
    asks for more takes it from its neighbours, PERF.md PR 26).  Results:
    forward ``o`` in the ``[B, S, H_v * d]`` view and the float32 state at
    each block's start; backward ``dq``, ``dk`` (summed over the value heads
    of a key head), ``dv`` and the gate rows' cotangent."""
    b, s, h_k, h_v, d = QWEN3_NEXT_RULE
    assert G.tiles(64, d, d, h_v // h_k)
    assert G._PARAMS.vmem_limit_bytes is None
    qk = _aval(one_chip, (b, s, h_k, d), jnp.bfloat16)
    v = _aval(one_chip, (b, s, h_v, d), jnp.bfloat16)
    gate = _aval(one_chip, (b, s, h_v), jnp.float32)

    def loss(q, k, v, g, beta):
        out = G.gated_delta_rule(q, k, v, g, beta, chunk_size=64)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=range(5)), qk, qk, v, gate, gate)
    shapes = _kernel_shapes(text)
    blocks, rows = s // (16 * 64), s // 128
    want = [[f"bf16[{b},{s},{h_v * d}]", f"f32[{b},{h_v},{blocks},{d},{d}]"],
            [f"bf16[{b},{s},{h_k * d}]", f"bf16[{b},{s},{h_k * d}]",
             f"bf16[{b},{s},{h_v * d}]", f"f32[{b},{h_v},{rows},8,128]"]]
    assert sorted(shapes) == sorted(want)


# the routed layers of the benchmark's two routed cells: (rows_bound, hidden,
# expert width, experts held)
GROUPED = {"nemotron_h": (18432, 2688, 1856, 8),
           "qwen3_next": (40960, 2048, 512, 16)}


def _grouped_kernels(one_chip, m, k, n, g, dtype):
    """The compiled text and the kernels' result shapes of the grouped
    product ``[m, k] x [g, k, n]`` with both its gradients, rows in
    ``dtype`` on float32 weights."""
    def loss(a, w, sizes):
        out = M.grouped_matmul(a, w, M.row_tiles(sizes, m))
        return jnp.sum(out * out)

    text = _compile(jax.grad(loss, argnums=(0, 1)),
                    _aval(one_chip, (m, k), dtype),
                    _aval(one_chip, (g, k, n), jnp.float32),
                    _aval(one_chip, (g,), jnp.int32))
    return text, sorted(_kernel_shapes(text))


@pytest.mark.parametrize("cell", sorted(GROUPED))
@pytest.mark.parametrize("down", [False, True], ids=["up", "down"])
def test_grouped_matmul_benchmark_shapes_and_signatures(one_chip, cell, down):
    """The grouped product and its two backward products at a routed
    layer's shapes (``[m, hidden] x [g, hidden, width]`` and its transpose),
    bfloat16 rows on float32 weights, inside the VMEM an operation may scope
    by default (no limit is passed).  Results: the product in float32, the
    rows' cotangent in bfloat16, the weights' gradient in float32."""
    m, d, f, g = GROUPED[cell]
    k, n = (f, d) if down else (d, f)
    assert M.tiles(m, k, n, g, 2) is not None
    assert M._PARAMS.vmem_limit_bytes is None
    text, shapes = _grouped_kernels(one_chip, m, k, n, g, jnp.bfloat16)
    assert shapes == sorted(
        [[f"f32[{m},{n}]"], [f"bf16[{m},{k}]"], [f"f32[{g},{k},{n}]"]])
    assert "vmem_limit_bytes" not in text
    assert "ragged-dot" not in text


def test_grouped_matmul_tiled_contraction_compiles(one_chip):
    """A contraction too long to lie whole beside a row tile (float32,
    8,192 wide) is summed over tiles in VMEM scratch: the one form of the
    product no benchmark cell runs."""
    m, k, n, g = 2048, 8192, 384, 4
    assert M.tiles(m, k, n, g, 4).product[0] < k
    _, shapes = _grouped_kernels(one_chip, m, k, n, g, jnp.float32)
    assert shapes == sorted(
        [[f"f32[{m},{n}]"], [f"f32[{m},{k}]"], [f"f32[{g},{k},{n}]"]])


# the attention layer and a Mamba-2 layer of the benchmark's Nemotron-H cell
NEMOTRON_H_ATTN = (2, 8192, 32, 128)
NEMOTRON_H_KV_HEADS = 2
NEMOTRON_H_TOKENS = (2, 8192)


def test_flash_attention_nemotron_h_shape_and_signatures(one_chip):
    """D = 128, sixteen query heads to a K/V head, S = 8,192, no rotary and
    no gate: a head is one whole lane block of the projections' (B, S, H*D)
    layout, and a K/V head's 16 x 128 = 2,048 query lanes are what
    Qwen3-Next's 8 x 256 are.  One head's K and V rows, double-buffered,
    pass the 14 MiB budget (16.3 MB reckoned), so by their own rule the
    kernels take one head a program under the raised limit and walk the k
    tiles in a loop.  The result signatures are those
    ``benchmark/layer_metrics/full_attn_roofline.py`` tells the kernels
    apart by."""
    b, s, h, d = NEMOTRON_H_ATTN
    group = h // NEMOTRON_H_KV_HEADS
    heads = F._Heads(d, F._pack(h, group, d, 128))
    assert heads.pack == 1
    assert F._pick_heads(heads, F._together(heads, b * h, h, group, False),
                         s, s, 2, 512, 512) == 1
    assert not F._prefix(True, s, s, 512, s)
    assert F._VMEM_BUDGET < F._vmem_bytes(heads, 1, s, s, 2, 512, 512) \
        == 16252928 <= F._VMEM_LIMIT * 3 // 4
    q = _aval(one_chip, (b, s, h, d), jnp.bfloat16)
    kv = _aval(one_chip, (b, s, NEMOTRON_H_KV_HEADS, d), jnp.bfloat16)

    def loss(q, k, v):
        out = F.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    want = [[f"bf16[{b},{s},{h * d}]", f"f32[{b * h},1,{s}]"],
            [f"bf16[{b},{s},{h * d}]"],
            [f"f32[{b},{s},{h * d}]", f"f32[{b},{s},{h * d}]"]]
    assert sorted(_kernel_shapes(text)) == sorted(want)


def test_mamba2_layer_nemotron_h_fits_its_share_of_the_step(one_chip):
    """One Mamba-2 mixer at the cell's 16,384 tokens and published widths,
    forward and backward in bfloat16 (no kernel: the chunked scan is matrix
    products and a ``lax.scan``): the compiler takes it, and its temporaries
    stay under 2.5 GB, so the blocks of chunks of ``ops/ssd.py`` and the
    checkpointed float32 stretches do what they are there for (the ``L``
    tiles alone are 0.54 GB in float32 were they kept for the sequence)."""
    from autodist_tpu.models.nemotron_h import Mamba2Mixer, NemotronHConfig

    mixer = Mamba2Mixer(NemotronHConfig())
    x = _aval(one_chip, NEMOTRON_H_TOKENS + (2688,), jnp.bfloat16)
    params = jax.tree.map(
        lambda a: _aval(one_chip, a.shape, a.dtype),
        jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)["params"])

    def loss(p, x):
        return jnp.sum(mixer.apply({"params": p}, x).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile(compiler_options=TPU_DEFAULTS)
    text = compiled.as_text()
    assert "ssd.scan" in text and "ssd.proj" in text
    assert "tpu_custom_call" not in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"Mamba-2 layer, 16,384 tokens: temp_size_in_bytes {temp}")
    assert temp < 2.5e9, temp


# the attention layer and a routed layer of the benchmark's LFM2 cell
LFM2_ATTN = (4, 8192, 32, 64)
LFM2_KV_HEADS = 8
# tokens, rows_bound, hidden, expert width, experts, experts held, a token's
LFM2_ROUTED = (4 * 8192, 49152, 2048, 1792, 32, 8, 4)


def _scoped_vmem(text):
    """The bytes of scoped VMEM the compiler says each ``tpu_custom_call`` of
    a compiled text uses."""
    return [int(m) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in re.findall(
                r'"used_scoped_memory_configs":\[\{"memory_space":"1",'
                r'"offset":"0","size":"(\d+)"', line)]


def test_flash_attention_lfm2_shape_and_signatures(one_chip):
    """D = 64, four query heads to a K/V head, S = 8,192: grouped K/V heads
    under 128 lanes have no place in the projections' layout (two query
    heads of a lane block would share half a K/V lane block), so ``_pack``
    says 0 and the kernels read the FOLDED layout, ``(B*H, S, D)`` made by a
    transpose, a head's 64 lanes padded to 128.  One head's rows,
    double-buffered, pass the 14 MiB budget, so a program takes one head
    under the raised limit and walks the k tiles in a loop.  What the rule
    reckons (16.25 MB) covers what the compiler says each kernel uses.  The
    result signatures are those
    ``benchmark/layer_metrics/full_attn_roofline.py`` tells the kernels
    apart by, folded too."""
    b, s, h, d = LFM2_ATTN
    group = h // LFM2_KV_HEADS
    heads = F._Heads(d, F._pack(h, group, d, 128))
    assert heads.pack == 0
    assert F._together(heads, b * h, h, group, False) == group
    assert F._pick_heads(heads, group, s, s, 2, 512, 512) == 1
    assert not F._prefix(True, s, s, 512, s)
    reckoned = F._vmem_bytes(heads, 1, s, s, 2, 512, 512)
    assert F._VMEM_BUDGET < reckoned == 16252928 <= F._VMEM_LIMIT * 3 // 4
    q = _aval(one_chip, (b, s, h, d), jnp.bfloat16)
    kv = _aval(one_chip, (b, s, LFM2_KV_HEADS, d), jnp.bfloat16)

    def loss(q, k, v):
        out = F.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    want = [[f"bf16[{b * h},{s},{d}]", f"f32[{b * h},1,{s}]"],
            [f"bf16[{b * h},{s},{d}]"],
            [f"f32[{b * h},{s},{d}]", f"f32[{b * h},{s},{d}]"]]
    assert sorted(_kernel_shapes(text)) == sorted(want)
    used = _scoped_vmem(text)
    print(f"LFM2 attention kernels: scoped VMEM {sorted(used)}, "
          f"reckoned {reckoned}")
    assert len(used) == 3 and max(used) <= reckoned


def test_routed_layer_lfm2_fits_its_share_of_the_step(one_chip, monkeypatch):
    """One routed layer at the cell's 32,768 tokens, 49,152 packed rows and
    published widths (sigmoid scores over 32 experts, four a token, the 8
    held as SwiGLU of 2,048 x 1,792), forward and backward in bfloat16,
    through ``expert_layer`` as a TPU runs it: the nine grouped products are
    the repo's kernels at the tiles their rule takes for these widths (1,792
    is 3.5 x 512, no multiple of the compiler's tile), inside the VMEM an
    operation may scope by default, and the layer's temporaries (2.07 GB)
    stay under 2.5 GB."""
    from autodist_tpu.parallel.moe import expert_layer

    t, m, d, f, experts, held, k = LFM2_ROUTED
    for down in (False, True):
        assert M.tiles(m, *((f, d) if down else (d, f)), held, 2) == M.Tiles(
            256, (f, 1024) if down else (d, 896),
            (d, 896) if down else (f, 1024),
            (640, 1024) if down else (1024, 640))
    monkeypatch.setattr(F, "_on_tpu", lambda: True)

    def loss(x, w_r, bias, w_gate, w_up, w_down):
        y, stats = expert_layer(
            x, w_r, w_gate, w_up, w_down, top_k=k, rows_bound=m,
            score=jax.nn.sigmoid, select_bias=bias, scale=1.0, norm_eps=1e-6)
        return jnp.sum(y.astype(jnp.float32)) + stats["overflow_rows"]

    avals = [_aval(one_chip, (t, d), jnp.bfloat16),
             _aval(one_chip, (d, experts), jnp.float32),
             _aval(one_chip, (experts,), jnp.float32),
             _aval(one_chip, (held, d, f), jnp.float32),
             _aval(one_chip, (held, d, f), jnp.float32),
             _aval(one_chip, (held, f, d), jnp.float32)]
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        *avals).compile(compiler_options=TPU_DEFAULTS)
    text = compiled.as_text()
    assert "ragged-dot" not in text and "vmem_limit_bytes" not in text
    shapes = _kernel_shapes(text)
    assert sorted(shapes) == sorted(
        [[f"f32[{m},{f}]"]] * 2 + [[f"f32[{m},{d}]"]]            # forward
        + [[f"bf16[{m},{d}]"]] * 2 + [[f"bf16[{m},{f}]"]]        # rows
        + [[f"f32[{held},{d},{f}]"]] * 2 + [[f"f32[{held},{f},{d}]"]])
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"LFM2 routed layer, 32,768 tokens, 49,152 rows: "
          f"temp_size_in_bytes {temp}")
    assert temp < 2.5e9, temp


def test_flash_block_update_ring_step(one_chip):
    # one ring step of a device that holds S=1024 positions of 2 x 12 heads
    bh, s, d = 2 * GPT_ATTN[2], 1024, GPT_ATTN[3]
    qkv = _aval(one_chip, (bh, s, d), jnp.bfloat16)
    ml = _aval(one_chip, (bh, s), jnp.float32)
    o = _aval(one_chip, (bh, s, d), jnp.float32)
    off = _aval(one_chip, (), jnp.int32)

    def step(q, k, v, m, l, o, q_off, k_off):
        out = F.flash_block_update(q, k, v, m, l, o, q_off, k_off,
                                   causal=True, interpret=False)
        assert out is not None, "S=1024 must tile for the compiled kernel"
        return out

    _compile(step, qkv, qkv, qkv, ml, ml, o, off, off)


# ------------------------------------------------------ int8 wire codecs --

@pytest.fixture(scope="module")
def gpt_small_bucket_blocks():
    """``(n_dev, blocks per chunk)`` of the largest gradient bucket the
    engine plans for GPT-2-small under an int8 codec on four replicas, tiled
    as ``Int8Compressor.all_reduce`` tiles it for the kernels."""
    import optax

    from autodist_tpu.kernel.graph_transformer import GraphTransformer
    from autodist_tpu.model_item import ModelItem
    from autodist_tpu.models.gpt import GPT, GPT_SMALL
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce
    from autodist_tpu.strategy.base import StrategyCompiler
    from autodist_tpu.utils.rng import host_key

    n_dev = 4
    shapes = jax.eval_shape(lambda: GPT(GPT_SMALL).init(
        host_key(0), jnp.zeros((1, 8), jnp.int32),
        return_hidden=True))["params"]
    spec = ResourceSpec.from_num_chips(n_dev)
    item = ModelItem(lambda p, b: 0.0, shapes, optax.sgd(0.1))
    strategy = StrategyCompiler(item, spec).compile(
        AllReduce(compressor="Int8Compressor").build(item, spec))
    t = GraphTransformer(strategy, item,
                         Mesh(np.array(jax.devices()[:n_dev]), ("replica",)))
    n = max(sum(b.sizes) for b in t.buckets)
    tile = Q.ROWS * Q.BLOCK
    chunk = -(-(-(-n // n_dev)) // tile) * tile   # compressor.py: use_pallas
    return n_dev, chunk // Q.BLOCK


def test_int8_codec_kernels_gpt_small_bucket(one_chip,
                                             gpt_small_bucket_blocks):
    n_dev, blocks = gpt_small_bucket_blocks
    assert blocks * Q.BLOCK * n_dev > 60e6      # over half of GPT-2-small
    x = _aval(one_chip, (n_dev * blocks, Q.BLOCK), jnp.float32)
    q_rx = _aval(one_chip, (n_dev, blocks, Q.BLOCK), jnp.int8)
    s_rx = _aval(one_chip, (n_dev, blocks, 1), jnp.float32)
    _compile(lambda x: Q.quantize_int8(x, interpret=False), x)
    _compile(lambda q, s: Q.dequant_sum(q, s, interpret=False), q_rx, s_rx)
    _compile(lambda q, s: Q.equarx_hop(q, s, n_dev, interpret=False),
             q_rx, s_rx)


# ----------------------------------------------------------- fused norms --

def _largest_fitting_rows(fits, hi=1 << 16):
    return max(r for r in range(N.SUB, hi, N.SUB) if fits(r))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_fused_batch_norm_at_the_guard(one_chip, dtype, grad):
    """The guard is what the compiler accepts: at the widest ResNet-50
    channel count, the tallest slab ``bn_fits_vmem`` admits compiles, plain
    and with residual + relu."""
    ch = 2048
    for residual in (False, True):
        rows = _largest_fitting_rows(lambda r: N.bn_fits_vmem(
            jax.ShapeDtypeStruct((r, ch), dtype), residual=residual))
        x = _aval(one_chip, (rows, ch), dtype)
        sb = _aval(one_chip, (ch,), jnp.float32)

        def loss(x, scale, bias, res):
            y, mean, var = N.fused_batch_norm(
                x, scale, bias, act="relu" if residual else None,
                residual=res if residual else None, interpret=False)
            return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(mean)
                    + jnp.sum(var))

        _compile(jax.grad(loss, argnums=(0, 1, 2, 3)) if grad else loss,
                 x, sb, sb, x)


def test_resnet50_b256_batch_norm_sites_are_all_over_the_guard():
    # the finding ROADMAP S2 rests on: at B=256 the whole-slab kernel fits
    # at no ResNet-50 site, so norm="bn_fused" is the reference everywhere
    for rows, ch in RESNET50_B256_BN_SITES:
        assert not N.bn_fits_vmem(
            jax.ShapeDtypeStruct((rows, ch), jnp.bfloat16)), (rows, ch)


def test_fused_batch_norm_module_over_the_guard_runs_the_reference(
        one_chip, monkeypatch):
    from autodist_tpu.utils import logging

    shape = (256, 7, 7, 2048)       # ResNet-50 B=256's smallest BN site
    said = []
    monkeypatch.setattr(logging, "warning",
                        lambda msg, *a: said.append(msg % a))
    monkeypatch.setattr(N, "_on_tpu", lambda: True)  # interpret=False
    mod = FusedBatchNorm(use_running_average=False, dtype=jnp.bfloat16)
    variables = jax.eval_shape(
        lambda: mod.init(jax.random.key(0), jnp.zeros(shape, jnp.bfloat16)))

    def fwd(v, x):
        return mod.apply(v, x, mutable=["batch_stats"])[0]

    avals = jax.tree.map(lambda a: _aval(one_chip, a.shape, a.dtype),
                         variables)
    text = jax.jit(fwd).lower(
        avals, _aval(one_chip, shape, jnp.bfloat16)).compile(
            compiler_options=TPU_DEFAULTS).as_text()
    assert "tpu_custom_call" not in text
    assert any(str(shape) in s and "reference" in s for s in said), said


def test_fused_group_norm_largest_admitted_resnet50_site(one_chip):
    admitted = [(r, c) for r, c in RESNET50_GN_SITES if N.gn_fits_vmem(
        jax.ShapeDtypeStruct((256, r, c), jnp.bfloat16))]
    assert (112 * 112, 64) not in admitted       # the stem's slab is over
    rows, ch = max(admitted, key=lambda rc: rc[0] * max(rc[1], N.LANE))
    assert (rows, ch) == (56 * 56, 256)
    x = _aval(one_chip, (256, rows, ch), jnp.bfloat16)
    sb = _aval(one_chip, (ch,), jnp.float32)

    def loss(x, scale, bias):
        y = N.fused_group_norm(x, scale, bias, 32, interpret=False)
        return jnp.sum(y.astype(jnp.float32))

    _compile(loss, x, sb, sb)
    # (value_and_grad: the backward is closed-form jnp, and without the
    # value nothing would need the kernel's output)
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, sb, sb)
    # and over the guard the compiler does refuse: the guard is not slack
    # by an order of magnitude
    over = _aval(one_chip, (256, 112 * 112, 64), jnp.bfloat16)
    sb64 = _aval(one_chip, (64,), jnp.float32)
    with pytest.raises(Exception, match="vmem|VMEM"):
        jax.jit(loss).lower(over, sb64, sb64).compile(
            compiler_options=TPU_DEFAULTS)

"""The ragged attention kernel, and the step programs round it, lowered
for the chip this repo serves on, without the chip: the TPU's compiler
is installed here and compiles for a v5e that is described, not
attached.

What it guards: the custom call that carries the kernel must depend on
the kernel alone.  Mosaic serializes the kernel's MLIR module into the
call's payload, and with Python tracebacks in its locations the payload
(and so the compilation cache's key) changed with the line and the depth
of the stack the kernel was reached from: a traced and an untraced run
of one cell each compiled their own copy of every step program.
`configure_compile_cache()` (core/runtime.py) lowers without Python
frames; the suite's conftest calls it, as every entry point does.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library, and every pytest worker imports
every test file.  Keep these tests in this one file."""
import math
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import kv_page_write as kw
from paddle_tpu.ops.pallas import paged_attention as pa

# (query heads, K/V heads, head size) of the benchmark's configurations
WIDTHS = {"mistral-7b": (32, 8, 128), "yi-1.5-6b": (32, 4, 128)}
BLOCK, NUM_BLOCKS, NBLK, ROWS = 16, 4097, 256, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """The kernel itself, not its interpreter (off the TPU the module
    resolves to interpret mode by default)."""
    monkeypatch.setattr(pa, "INTERPRET", False)


def _shapes(one_chip, model, tq, *, quant=False, num_blocks=NUM_BLOCKS):
    h, kvh, d = WIDTHS[model]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    page = jnp.int8 if quant else jnp.bfloat16
    pool = sds((num_blocks, kvh, 32 if quant else BLOCK, d), page)
    scales = (sds((num_blocks, kvh), jnp.float32),) * 2 if quant else ()
    return (sds((tq, h, d), jnp.bfloat16), pool, pool) + scales + (
        sds((ROWS + 1, NBLK), jnp.int32), sds((ROWS + 1,), jnp.int32),
        sds((ROWS,), jnp.int32))


def _payloads(text: str) -> list:
    return re.findall(r'backend_config\s*=\s*"((?:[^"\\]|\\.)*)"', text)


def _direct(*a):
    return pa.ragged_paged_attention_packed(*a)


def _inner(*a):
    x = a[0] * 1                # another line ...
    return pa.ragged_paged_attention_packed(x, *a[1:])


def _through_a_wrapper(*a):
    with jax.named_scope("attn"):
        return _inner(*a)       # ... and another depth of the stack


@pytest.mark.parametrize("tq", [32, 64, 128, 192])
@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_kernel_payload_is_independent_of_the_call_stack(
        one_chip, compiled_kernels, model, tq):
    args = _shapes(one_chip, model, tq)
    a = jax.jit(_direct).lower(*args).as_text()
    b = jax.jit(_through_a_wrapper).lower(*args).as_text()
    pa_, pb_ = _payloads(a), _payloads(b)
    assert len(pa_) == len(pb_) == 1
    assert pa_ == pb_                      # byte for byte
    assert "tpu_custom_call" in a
    assert 'kernel_name = "ragged_paged_attention"' in a
    assert __file__ not in a and "test_chip_lowering" not in pa_[0]


def test_int8_page_kernel_is_named_and_stack_independent(
        one_chip, compiled_kernels):
    args = _shapes(one_chip, "mistral-7b", 32, quant=True)

    def direct(*a):
        return pa.ragged_paged_attention_quant_packed(*a)

    def wrapped(*a):
        return direct(*a)

    a = jax.jit(direct).lower(*args).as_text()
    b = jax.jit(wrapped).lower(*args).as_text()
    assert _payloads(a) == _payloads(b) and len(_payloads(a)) == 1
    assert 'kernel_name = "ragged_paged_attention_q8"' in a


def test_entry_points_lower_without_python_frames_and_keep_scopes(
        one_chip, compiled_kernels):
    """What holds the payload still is one process-wide option of JAX's
    that every entry point sets with its compile cache (the suite's
    conftest too): no Python frames in MLIR locations.  The scope and
    operation names, which `LLMEngine.program_scopes()` reads back from
    the compiled text, stay."""
    from paddle_tpu.core.runtime import configure_compile_cache
    configure_compile_cache()
    assert jax.config.jax_traceback_in_locations_limit == 0
    text = jax.jit(_through_a_wrapper).lower(
        *_shapes(one_chip, "yi-1.5-6b", 32)).as_text(debug_info=True)
    assert ('loc("jit(_through_a_wrapper)/attn/ragged_paged_attention/'
            'pallas_call"') in text
    assert ".py" not in text


@pytest.fixture()
def no_persistent_cache():
    """An entry written for a described chip cannot be read back, and
    the next run would warn: the persistent cache is off around a
    compile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("tq", [32, 64, 128, 192])
@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_the_kernel_compiles_for_the_v5e(one_chip, compiled_kernels,
                                         no_persistent_cache, model, tq):
    """Every bucket of both configurations, at the benchmark's 256-page
    rows and [33, 256] table, through the chip's own compiler: what it
    refuses here it refuses on the chip (tiling, fast memory).  The
    result keeps the shape the trace is read by."""
    args = _shapes(one_chip, model, tq)
    compiled = jax.jit(_direct).lower(*args).compile()
    text = compiled.as_text()
    h, kvh, d = WIDTHS[model]
    assert "ragged_paged_attention" in text
    assert re.search(rf"bf16\[{tq},{kvh},{h // kvh},{d}\][^\n]* custom-call\(",
                     text)


def test_the_int8_page_kernel_compiles_for_the_v5e(one_chip,
                                                   compiled_kernels,
                                                   no_persistent_cache):
    """The same body with a dequantising load, at the largest int8 pool
    the scalar memory takes beside the table."""
    kvh = WIDTHS["mistral-7b"][1]
    pool = max(n for n in range(8, 4096, 8) if pa.ineligible(
        32, kvh, 128, 32, jnp.int8, launch=(ROWS + 1, NBLK, n)) is None)
    args = _shapes(one_chip, "mistral-7b", 32, quant=True, num_blocks=pool)
    text = jax.jit(pa.ragged_paged_attention_quant_packed).lower(
        *args).compile().as_text()
    assert "ragged_paged_attention_q8" in text


@pytest.mark.parametrize("tq,kvh,pages,bs,d,dt", [
    *[(tq, kvh, pages, BLOCK, 128, jnp.bfloat16)
      for kvh, pages in ((4, 4097), (8, 4097), (10, 1569))
      for tq in (32, 192, 576)],
    # the other pages the attention kernel's claim admits (``pa.
    # ineligible``: float pages of any multiple of 8 slots, heads of any
    # multiple of 128): a bf16 page-head of 8 rows is half a packed tile
    (32, 8, 4097, 8, 128, jnp.bfloat16),
    (192, 8, 4097, 8, 128, jnp.bfloat16),
    (192, 8, 2049, 32, 128, jnp.bfloat16),
    (192, 4, 1025, 24, 128, jnp.bfloat16),
    (192, 8, 1025, 64, 128, jnp.bfloat16),
    (192, 4, 2049, 16, 256, jnp.bfloat16),
    (192, 8, 2049, 8, 128, jnp.float32),
    (192, 8, 2049, 16, 128, jnp.float32),
    # ... and the widest table it admits ("table"): the writer's four
    # scratch lists lie in the scalar memory beside it
    (576, 8, 4097, 8, 128, "table"),
])
def test_the_page_writer_compiles_for_the_v5e(one_chip, compiled_kernels,
                                              no_persistent_cache, tq, kvh,
                                              pages, bs, d, dt):
    """The page writer at the benchmark's pools (Yi's and Mistral's of
    all eight layers, Phi-4-mini-flash's eight window layers of 10 heads)
    and at the other page shapes the engine would hand it (it runs
    wherever the attention kernel does), through the chip's own
    compiler: the rolled load of a page's rows from whole float32 tiles,
    the page copies both ways, its scalar memory beside the [33, 256]
    table.  The donated pools come back in their own buffers and nothing
    else has their shape."""
    nblk = NBLK
    if dt == "table":
        dt = jnp.bfloat16
        nblk = max(n for n in range(128, 1 << 15, 128) if pa.ineligible(
            32, kvh, d, bs, dt, launch=(ROWS + 1, n, pages)) is None)

    def sds(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = (8, pages, kvh, bs, d)
    text = jax.jit(kw.kv_page_write, donate_argnums=(2, 3)).lower(
        sds((tq, kvh, d)), sds((tq, kvh, d)), sds(pool), sds(pool),
        sds((ROWS + 1, nblk), jnp.int32), sds((ROWS + 1,), jnp.int32),
        sds((ROWS,), jnp.int32), sds((), jnp.int32)).compile().as_text()
    _, comps = _computations(text)
    stacked = "{}[{}]".format("bf16" if dt == jnp.bfloat16 else "f32",
                              ",".join(map(str, pool)))
    assert len(_page_writer_calls(comps, stacked)) == 1
    made = [line for lines in comps.values() for line in lines
            if re.search(rf"= \(?{re.escape(stacked)}", line)
            and not re.search(r" (?:parameter|custom-call|get-tuple-element"
                              r"|bitcast|tuple)\(", line)]
    assert not made, made[0][:300]


def test_prefetched_operands_are_what_the_claim_counts(one_chip,
                                                       compiled_kernels):
    """`scalar_prefetch_bytes` counts the operands the launch prefetches:
    cu, kv_lens, the table (no per-token seg or rel) and the layer index,
    and both scale pools over int8 pages, in the (8, 128) tiles of 32-bit
    words scalar memory holds them in."""
    def prefetched(fn, args):
        jaxpr = jax.make_jaxpr(fn)(*args)
        eqn, = [e for e in jaxpr.jaxpr.eqns if "pallas" in e.primitive.name]
        n = eqn.params["grid_mapping"].num_index_operands
        return [v.aval for v in eqn.invars[:n]]

    def tiled(aval):
        rows, cols = (1,) * (2 - aval.ndim) + aval.shape
        return -(-rows // 8) * 8 * -(-cols // 128) * 128 * 4

    kvh = WIDTHS["mistral-7b"][1]
    ops = prefetched(_direct, _shapes(one_chip, "mistral-7b", 192))
    assert [a.shape for a in ops] == [(ROWS + 1,), (ROWS,),
                                      (ROWS + 1, NBLK), (1,)]
    # a 1-D operand takes whole lanes, not a tile of eight rows: cu,
    # kv_lens and the layer index
    assert pa.scalar_prefetch_bytes(ROWS + 1, NBLK, NUM_BLOCKS, kvh, False) \
        == 3 * 128 * 4 + tiled(ops[2])
    ops = prefetched(pa.ragged_paged_attention_quant_packed,
                     _shapes(one_chip, "mistral-7b", 32, quant=True))
    assert [a.shape for a in ops[3:]] == [(1,)] + [(NUM_BLOCKS, kvh)] * 2
    assert pa.scalar_prefetch_bytes(ROWS + 1, NBLK, NUM_BLOCKS, kvh, True) \
        == 3 * 128 * 4 + tiled(ops[2]) + sum(tiled(a) for a in ops[4:])


# ---------------------------------------------------------------------------
# the latent-attention model's kernels (PR 28), at sarvam-105b-ep4's sizes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tq", [32, 576])
def test_the_latent_kernel_compiles_for_the_v5e(one_chip, compiled_kernels,
                                                no_persistent_cache, tq):
    """64 heads on one 640-wide stored row (576 cached numbers), the pool
    of all six layers left in HBM and read at a layer index, the
    [33, 1024] table of 16,384-token rows.  A pool declared 576 wide is
    what the chip's compiler refused (its rows are stored 640 wide, and a
    copy of whole rows has to say so)."""
    from paddle_tpu.ops.pallas import mla_attention as mla

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    assert mla.ineligible(64, 640, 512, 16, jnp.bfloat16,
                          launch=(33, 1024, 16385)) is None
    args = (sds((tq, 64, 576), jnp.bfloat16),
            sds((6, 16385, 16, 640), jnp.bfloat16),
            sds((33, 1024), jnp.int32), sds((33,), jnp.int32),
            sds((32,), jnp.int32))
    text = jax.jit(lambda q, pool, bt, cu, kvl:
                   mla.ragged_latent_attention_packed(
                       q, pool, 3, bt, cu, kvl, latent_dim=512,
                       sm_scale=0.1)).lower(*args).compile().as_text()
    assert "ragged_latent_attention" in text
    assert re.search(r"bf16\[\d+,512\][^\n]* custom-call\(", text)


@pytest.mark.parametrize("rows", [256, 4608])
def test_the_grouped_expert_kernel_compiles_for_the_v5e(
        one_chip, compiled_kernels, no_persistent_cache, rows):
    """32 experts of 4096 x 2048: both halves of an expert's SwiGLU, at a
    decode-sized and at a 576-token step's pairs."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bf = jnp.bfloat16
    sizes = sds((32,), jnp.int32)
    up = jax.jit(lambda x, g, u, s: gm.grouped_swiglu(
        x, g, u, s, use_kernel=True)).lower(
            sds((rows, 4096), bf), sds((32, 4096, 2048), bf),
            sds((32, 4096, 2048), bf), sizes).compile().as_text()
    down = jax.jit(lambda a, w, s: gm.grouped_matmul(
        a, w, s, use_kernel=True)).lower(
            sds((rows, 2048), bf), sds((32, 2048, 4096), bf),
            sizes).compile().as_text()
    assert "grouped_expert_matmul" in up and "grouped_expert_matmul" in down


@pytest.mark.parametrize("window", [None, 4096])
@pytest.mark.parametrize("tq", [32, 64, 576])
def test_the_kernel_compiles_at_group_7_and_over_a_window(
        one_chip, compiled_kernels, no_persistent_cache, tq, window):
    """28 query heads over 4 K/V heads (a decode item is 7 score rows,
    not a multiple of the sublane 8), the pools of all layers of one
    kind read at a layer index, a [33, 1024] table: the global layers'
    launch and the window layers', which has a kernel name of its own."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, pages = (2, 32769) if window is None else (6, 9249)
    pool = sds((layers, pages, 4, BLOCK, 128), jnp.bfloat16)
    text = jax.jit(lambda q, k, v, bt, cu, kvl, l:
                   pa.ragged_paged_attention_packed(
                       q, k, v, bt, cu, kvl, layer=l, window=window)).lower(
        sds((tq, 28, 128), jnp.bfloat16), pool, pool,
        sds((ROWS + 1, 1024), jnp.int32), sds((ROWS + 1,), jnp.int32),
        sds((ROWS,), jnp.int32), sds((), jnp.int32)).compile().as_text()
    name = "ragged_paged_attention" if window is None \
        else pa.WINDOW_KERNEL_NAME
    assert re.search(rf"%{name}(\.\d+)? = bf16\[{tq},4,7,128\]", text)


def test_the_reglu_expert_kernel_compiles_for_the_v5e(
        one_chip, compiled_kernels, no_persistent_cache):
    """64 experts of 2560 x 768 with ReLU on the gate, at a 576-token
    step's 3,456 pairs."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bf = jnp.bfloat16
    sizes = sds((64,), jnp.int32)
    up = jax.jit(lambda x, g, u, s: gm.grouped_reglu(
        x, g, u, s, use_kernel=True)).lower(
            sds((3456, 2560), bf), sds((64, 2560, 768), bf),
            sds((64, 2560, 768), bf), sizes).compile().as_text()
    down = jax.jit(lambda a, w, s: gm.grouped_matmul(
        a, w, s, use_kernel=True)).lower(
            sds((3456, 768), bf), sds((64, 768, 2560), bf),
            sizes).compile().as_text()
    assert "grouped_expert_matmul" in up and "grouped_expert_matmul" in down


@pytest.mark.parametrize("heads,window", [(48, None), (64, 512)])
@pytest.mark.parametrize("tq", [32, 64, 576])
def test_the_kernel_compiles_at_groups_6_and_8_of_one_model(
        one_chip, compiled_kernels, no_persistent_cache, tq, heads, window):
    """Laguna-XS.2's two launches over 8 K/V heads: 48 query heads over
    whole contexts (a decode item is 6 score rows: off the sublane 8 as
    7 is) and 64 over a window of 512, 32 pages, SHORTER than a 512-token
    chunk: an item's page range ends before the chunk does."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    layers, pages = (2, 16385) if window is None else (5, 2081)
    pool = sds((layers, pages, 8, BLOCK, 128), jnp.bfloat16)
    text = jax.jit(lambda q, k, v, bt, cu, kvl, l:
                   pa.ragged_paged_attention_packed(
                       q, k, v, bt, cu, kvl, layer=l, window=window)).lower(
        sds((tq, heads, 128), jnp.bfloat16), pool, pool,
        sds((ROWS + 1, 1024), jnp.int32), sds((ROWS + 1,), jnp.int32),
        sds((ROWS,), jnp.int32), sds((), jnp.int32)).compile().as_text()
    name = "ragged_paged_attention" if window is None \
        else pa.WINDOW_KERNEL_NAME
    assert re.search(
        rf"%{name}(\.\d+)? = bf16\[{tq},8,{heads // 8},128\]", text)


@pytest.mark.parametrize("rows", [256, 4608])
def test_the_grouped_expert_kernel_compiles_at_256_small_groups(
        one_chip, compiled_kernels, no_persistent_cache, rows):
    """256 experts of 2048 x 512 (a whole 2 MB matrix a block), about 17
    rows a group at a 576-token step's 4,608 pairs and one at a decode
    step's 256: both halves of an expert's SwiGLU."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bf = jnp.bfloat16
    sizes = sds((256,), jnp.int32)
    up = jax.jit(lambda x, g, u, s: gm.grouped_swiglu(
        x, g, u, s, use_kernel=True)).lower(
            sds((rows, 2048), bf), sds((256, 2048, 512), bf),
            sds((256, 2048, 512), bf), sizes).compile().as_text()
    down = jax.jit(lambda a, w, s: gm.grouped_matmul(
        a, w, s, use_kernel=True)).lower(
            sds((rows, 512), bf), sds((256, 512, 2048), bf),
            sizes).compile().as_text()
    assert "grouped_expert_matmul" in up and "grouped_expert_matmul" in down


# ---------------------------------------------------------------------------
# the sampling epilogue's branch (PR 29), in a whole ragged step program
# ---------------------------------------------------------------------------

_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")


def _computations(text: str):
    """(entry name, {computation: its instruction lines})."""
    from paddle_tpu.inference.serving import _HLO_COMPUTATION
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = _HLO_COMPUTATION.match(line)
        if m:
            cur = m.group(1)
            comps[cur] = []
            if line.startswith("ENTRY"):
                entry = cur
        elif cur is not None and " = " in line:
            comps[cur].append(line)
    return entry, comps


def _branches(line: str) -> list:
    m = _BRANCHES.search(line)
    names = m.group(1).split(",") if m else re.findall(
        r"\b(?:true|false)_computation=%?([\w.\-]+)", line)
    return [n.strip().lstrip("%") for n in names]


def _reached(comps: dict, roots, *, through_conditionals: bool) -> set:
    """Computations reached from ``roots``; a conditional's branches are
    followed only where asked."""
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for line in comps[c]:
            if " conditional(" in line:
                if through_conditionals:
                    todo += _branches(line)
            else:
                todo += _CALLED.findall(line)
    return seen


def _tiny_engine(arch: str):
    from paddle_tpu.inference import LLMEngine
    if arch == "llama_dense":
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        model = LlamaForCausalLM(LlamaConfig.tiny(
            vocab=1000, hidden=128, layers=2, heads=4, ffn=256, seq=64))
    else:
        from paddle_tpu.models.mla_moe import (MlaMoeConfig,
                                               MlaMoeForCausalLM)
        model = MlaMoeForCausalLM(MlaMoeConfig.tiny(
            vocab=1000, hidden=128, layers=3, heads=4, experts=8, seq=64))
    return LLMEngine(model, max_num_seqs=4, block_size=8, max_model_len=64,
                     max_prefill_tokens=32, prefill_token_bucket=16)


@pytest.mark.parametrize("target", ["cpu", "v5e"])
@pytest.mark.parametrize("arch", ["llama_dense", "mla_moe"])
def test_the_sampled_chain_compiles_into_one_branch(
        arch, target, request, no_persistent_cache):
    """In the compiled text of a ragged step program the sampler's sorts
    (the ones over the vocabulary) lie in computations that only a
    conditional's branch reaches: the entry computation and the layer
    loop hold none, so an all-greedy launch executes none.  What runs in
    either branch still carries scope ``sample``, which is how
    ``program_scopes()`` files a traced operation under the sampler."""
    from paddle_tpu.inference.serving import _instruction_scopes
    V, Tq = 1000, 16
    eng = _tiny_engine(arch)
    structs = eng._ragged_arg_structs(Tq, placed=target == "cpu")
    if target == "v5e":
        chip = request.getfixturevalue("one_chip")
        structs = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            structs)
    text = eng._get_ragged_prog(Tq).lower(*structs).compile().as_text()
    entry, comps = _computations(text)
    conds = [l for ls in comps.values() for l in ls if " conditional(" in l]
    assert len(conds) == 1 and "/sample/cond" in conds[0]
    outside = _reached(comps, [entry], through_conditionals=False)
    inside = _reached(comps, _branches(conds[0]),
                      through_conditionals=True)
    assert entry in outside and not outside & inside

    def vocab_sorts(names):
        return [l for c in names for l in comps[c]
                if " sort(" in l and f",{V}]" in l.split(" sort(")[0]]

    assert not vocab_sorts(outside)
    assert len(vocab_sorts(inside)) == 3      # top-k's, top-p's two
    # both branches' instructions are the sampler's, by their op_name
    # (a scalar comparator or reducer carries a bare primitive's name,
    # and what XLA made up carries none: neither is a traced operation)
    scopes = _instruction_scopes(text)
    named = [scopes[m.group(1)]["op_name"] for c in inside
             for l in comps[c]
             for m in [re.match(r"\s+(?:ROOT\s+)?%?([\w.\-]+) = ", l)]
             if m and m.group(1) in scopes]
    paths = [n for n in named if n.startswith("jit(")]
    assert len(paths) >= 10
    assert all("/sample/cond/" in n for n in paths)


@pytest.mark.parametrize("target", ["cpu", "v5e"])
@pytest.mark.parametrize("arch", ["llama_dense", "mla_moe"])
def test_the_step_program_hands_the_host_one_small_vector(
        arch, target, request, no_persistent_cache):
    """Beside ``sampled`` (which the launch behind takes on the device)
    and the pools, a compiled step program has ONE output, int32 and a
    few hundred bytes: the tokens, the finiteness flags as 0/1 and, of a
    model with expert layers, its four counts (PR 38): one
    device-to-host transfer a launch, which the launch itself starts."""
    Tq = 16
    eng = _tiny_engine(arch)
    structs = eng._ragged_arg_structs(Tq, placed=target == "cpu")
    if target == "v5e":
        chip = request.getfixturevalue("one_chip")
        structs = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            structs)
    compiled = eng._get_ragged_prog(Tq).lower(*structs).compile()
    pools = eng._pools()
    front = compiled.out_info[:-len(pools)]
    assert [(o.shape, o.dtype) for o in compiled.out_info[-len(pools):]] \
        == [(p.shape, p.dtype) for p in pools]
    Lq, counted = eng._Lq, 4 * (arch == "mla_moe")
    assert [(o.shape, str(o.dtype)) for o in front] \
        == [((Lq,), "int32"), ((2 * Lq + counted,), "int32")]
    assert (2 * Lq + counted) * 4 < 1024


@pytest.mark.parametrize("target", ["cpu", "v5e"])
def test_the_token_handover_is_a_few_small_operations(
        target, request, no_persistent_cache):
    """What a launch dispatched ahead of the commit in front of it adds
    to the step program (PR 34): ``toks = where(src >= 0, prev[src],
    toks)`` under scope ``prev_tokens``, on [Tq] and [Lq] int32 and
    nothing larger (the TPU pads the index vector to one tile of 1024
    words), before the embedding; ``prev`` is not donated."""
    from paddle_tpu.inference.serving import _instruction_scopes
    Tq = 16
    eng = _tiny_engine("llama_dense")
    structs = eng._ragged_arg_structs(Tq, placed=target == "cpu")
    assert [s.shape for s in structs[-2:]] == [(eng._Lq,), (Tq,)]
    if target == "v5e":
        chip = request.getfixturevalue("one_chip")
        structs = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip),
            structs)
    fn, donate = eng._make_ragged_fn(Tq)
    assert tuple(donate) == (1, 2)                       # the pools alone
    text = eng._get_ragged_prog(Tq).lower(*structs).compile().as_text()
    scopes = _instruction_scopes(text)
    mine = {n for n, v in scopes.items() if "/prev_tokens/" in v["op_name"]}
    assert mine
    sizes = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m and m.group(1) in mine:
            sizes.append(math.prod(int(n or 1) for n in
                                   m.group(2).split(",") if n))
    assert sizes and max(sizes) <= max(Tq, eng._Lq, 1024)


# ---------------------------------------------------------------------------
# the dense step program reads and writes the K/V pools in place (PR 31)
# ---------------------------------------------------------------------------

_RESULT = re.compile(r"\s+(?:ROOT\s+)?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(")



def _page_writer_calls(comps: dict, *stacked: str) -> list:
    """The page writer's custom calls in a compiled program.  Each is
    held to this: its results are a K and a V pool of ALL layers (one of
    the ``stacked`` shapes, "bf16[8,4097,8,16,128]"), they are its sixth
    and seventh operands' buffers (``output_to_operand_aliasing``: the
    pools are written where they lie; without it XLA would make the
    results anew and copy a whole pool a layer), and those operands
    are taken from the loop's carry or the program's parameters as they
    are, not from a copy."""
    calls = []
    for lines in comps.values():
        made_by = {m.group(1): m.group(3) for m in map(_RESULT.match, lines)
                   if m}
        for call in lines:
            if " custom-call(" not in call or not re.match(
                    rf"\s+(?:ROOT\s+)?%?{kw.KERNEL_NAME}(?:\.\d+)? = ", call):
                continue
            calls.append(call)
            results, rest = call.split(" custom-call(", 1)
            assert any(results.count(s) == 2 for s in stacked), results[:300]
            assert ("output_to_operand_aliasing={{0}: (6, {}), "
                    "{1}: (7, {})}") in rest
            operands = re.findall(r"%([\w.\-]+)", rest.split(")", 1)[0])
            assert len(operands) == 8
            assert all(made_by[o] in ("parameter", "get-tuple-element")
                       for o in operands[6:]), operands[6:]
    return calls


def _dense_step_text(chip, monkeypatch, model, tq, quant, hidden=256,
                     ffn=512):
    """(compiled text, the pools' shape) of the engine's ragged step
    program at a configuration's attention widths, the benchmark's pool
    (4097 pages of 16 tokens; over int8 pages the largest pool the
    scalar memory takes, pages of 32) and eight scanned layers.  The
    engine is a tiny one told the head counts (its builder reads
    nothing else of the model), the arguments are shapes, and the model
    round the attention is as wide as asked (``hidden``, ``ffn``;
    narrow unless told: nothing of that size is allocated here, and
    nothing but a pool is then as large as a pool).  q, k and v lie as
    the engine holds them (``_out_major``: [L, heads, d, in])."""
    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.sampling import samp_structs
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    nh, kvh, d = WIDTHS[model]
    L, H, F, V = 8, hidden, ffn, 1000
    bs = 32 if quant else BLOCK
    nb = max(n for n in range(8, 4096, 8) if pa.ineligible(
        nh, kvh, d, bs, jnp.int8, launch=(ROWS + 1, NBLK, n)) is None) \
        if quant else NUM_BLOCKS
    eng = LLMEngine(
        LlamaForCausalLM(LlamaConfig.tiny(vocab=V, hidden=2 * d, layers=2,
                                          heads=2, ffn=64, seq=64)),
        max_num_seqs=ROWS, block_size=bs, max_model_len=64,
        max_prefill_tokens=192, prefill_token_bucket=64,
        kv_dtype="int8" if quant else "float32")
    monkeypatch.setattr(eng, "_nh", nh)
    monkeypatch.setattr(eng, "_kvh", kvh)
    monkeypatch.setattr(eng, "_attn", eng._attention_by_kind())
    monkeypatch.setattr(eng, "_platform", "tpu")
    eng.attention_path = eng._resolve_attention_path()
    assert eng._hd == d and eng.attention_path == "pallas"

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    layers = {"wq": (H, nh * d), "wk": (H, kvh * d), "wv": (H, kvh * d),
              "wo": (nh * d, H), "gate": (H, F), "up": (H, F),
              "down": (F, H)}
    for name in eng._out_major:
        width, out = layers[name]
        layers[name] = (out // d, d, width)
    params = {"embed": sds((V, H)), "head": sds((H, V)), "norm_f": sds((H,)),
              "layers": {"ln1": sds((L, H)), "ln2": sds((L, H)),
                         **{k: sds((L,) + v) for k, v in layers.items()}}}
    pool = (L, nb, kvh, bs, d)
    pools = (sds(pool, jnp.int8 if quant else jnp.bfloat16),) * 2
    if quant:
        pools += (sds(pool[:3], jnp.float32),) * 2 + (sds((nb,), jnp.bool_),)
    i32 = jnp.int32
    samp = jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype),
                                  samp_structs(eng._Lq, V))
    args = (params,) + pools + (
        sds((tq,), i32), sds((ROWS + 1,), i32), sds((ROWS,), i32),
        sds((ROWS + 1, NBLK), i32), sds((eng._Lq,), i32), samp,
        sds((eng._Lq,), i32), sds((tq,), i32))           # prev, src
    fn, donate = eng._make_ragged_fn(tq)
    return jax.jit(fn, donate_argnums=donate).lower(
        *args).compile().as_text(), pool


@pytest.mark.parametrize("pages", ["bf16", "int8"])
@pytest.mark.parametrize("tq", [32, 192])
@pytest.mark.parametrize("model", sorted(WIDTHS))
def test_the_dense_step_leaves_the_pools_where_they_lie(
        one_chip, compiled_kernels, no_persistent_cache, monkeypatch, model,
        tq, pages):
    """No instruction of the compiled step program makes anything as
    large as one layer of a K/V pool, whatever its shape or layout,
    but the writers of scope ``kv_write``, in place (the program's
    parameters are donated): over float pages the page writer's ONE
    custom call a layer, whose two results are the pools of all layers
    and alias its pool operands (``_page_writer_calls``); over int8
    pages the scatters.  What else may carry a pool's shape moves
    no byte: parameters, tuple elements, bitcasts.  And the kernel's
    custom call takes the pools of all layers, not a layer's slice.

    What this holds off: ``pool.at[layer, page, :, slot, :].set(rows)``
    writes the same values and fails here.  Its update window [Hkv, D]
    straddles the slot axis, so XLA lays the scatter's operand out
    slot-major ({4,2,3,1,0}), keeps the WHOLE pool so through the layer
    loop, and copies all of it (1.07 GB at Mistral's widths) back to
    row-major for the custom call in every layer: ``copy
    bf16[8,4097,8,16,128]`` under ``.../while/body/.../scatter``.  On a
    layer's slice (PR 30) the same window cost four layer-sized copies
    a layer: slice, slot-major, row-major, write back."""
    text, pool = _dense_step_text(one_chip, monkeypatch, model, tq,
                                  pages == "int8")
    _, comps = _computations(text)
    layer = math.prod(pool[1:])

    def writes_rows(line):
        return " scatter(" in line and "/kv_write/" in line

    large = []
    for lines in comps.values():
        for line in lines:
            m = _RESULT.match(line)
            if m and math.prod(int(n or 1) for n in
                               m.group(2).split(",")) >= layer:
                large.append((m.group(3), line))
    moved = [line for op, line in large
             if op not in ("parameter", "get-tuple-element", "bitcast")
             and not writes_rows(line)
             and not (op == "fusion" and any(
                 writes_rows(l) for c in _CALLED.findall(line)
                 for l in comps[c]))]
    assert not moved, moved[0][:300]
    stacked = "{}[{}]".format("s8" if pages == "int8" else "bf16",
                              ",".join(map(str, pool)))
    # over int8 pages K's and V's rows and the launch's whole pages,
    # re-encoded, once a layer; over float pages no scatter: K's and
    # V's pages in the writer's one call a layer
    assert sum(writes_rows(line) for _, line in large) \
        == (4 if pages == "int8" else 0)
    assert len(_page_writer_calls(comps, stacked)) \
        == (0 if pages == "int8" else 1)
    kernel, = [line for lines in comps.values() for line in lines
               if " custom-call(" in line and "ragged_paged_attention" in line]
    assert kernel.split("operand_layout_constraints=")[1].count(stacked) == 2


# ---------------------------------------------------------------------------
# the layer loop reads each weight inside its product and rewrites none
# (PR 40)
# ---------------------------------------------------------------------------

# (hidden, FFN) of the benchmark's dense configurations
REAL_WIDTHS = {"mistral-7b": (4096, 14336), "yi-1.5-6b": (4096, 11008)}
_MOVES_NOTHING = ("parameter", "get-tuple-element", "bitcast", "tuple")


def _moved_in_the_layer_loop(text: str, least: int) -> tuple:
    """(the instructions of the layer loop's body that MOVE ``least``
    elements or more, the fusions among its instructions that only slice
    that much out of a stacked operand).  What may be that large and
    moves nothing: parameters, tuple elements, bitcasts; what computes
    it: a fusion that holds a product; what writes it in place: the
    scatters of ``kv_write``."""
    _, comps = _computations(text)
    loop, = [line for lines in comps.values() for line in lines
             if " while(" in line and '/layers/while"' in line]
    body = comps[re.search(r"\bbody=%?([\w.\-]+)", loop).group(1)]

    def inside(line):
        return [(m.group(3), l) for c in _CALLED.findall(line)
                for l in comps[c] for m in [_RESULT.match(l)] if m]

    moved, slices = [], []
    for line in body:
        m = _RESULT.match(line)
        if not m or m.group(3) in _MOVES_NOTHING or math.prod(
                int(n or 1) for n in m.group(2).split(",")) < least:
            continue
        ops = inside(line) if m.group(3) == "fusion" else []
        if ops and all(op in _MOVES_NOTHING + ("constant", "dynamic-slice")
                       for op, _ in ops):
            slices.append(line)
        elif not any(op in ("convolution", "dot")
                     or (op == "scatter" and "/kv_write/" in l)
                     for op, l in ops):
            moved.append(line)
    return moved, slices


@pytest.mark.parametrize("model,tq", [("mistral-7b", 32), ("yi-1.5-6b", 192)])
def test_the_layer_loop_rewrites_no_weight(one_chip, compiled_kernels,
                                           no_persistent_cache, monkeypatch,
                                           model, tq):
    """At the configurations' REAL hidden and FFN widths (shapes only),
    inside the layer loop no ``copy``, and no fusion but a product or a
    ``kv_write`` scatter, has a result as large as one layer of
    ``wk``: every weight is sliced inside the product that reads it,
    and nothing is rewritten.

    It FAILS on the parent's layout (q, k, v stacked [L, hidden, out]
    and ``h @ w``): XLA lays q, k and v out head-major for the kernel,
    reads each weight as [heads, d, hidden] in ``qkv/dot_general``, and
    gets there by slicing the layer into fast memory and transposing
    the whole matrix, in every layer of every step: at Mistral's widths
    ``constant_dynamic-slice_fusion.6`` then ``copy.91 =
    bf16[1,4096,4096]{1,2,0 ... S(1)}`` for ``wq``,
    ``constant_dynamic-slice_fusion.7`` / ``copy.98`` and ``.8`` /
    ``copy.104`` = ``bf16[1,4096,1024]{1,2,0}`` for ``wk`` and ``wv``
    (ISSUE 40's compile numbered them ``copy.105``, ``.112``, ``.118``;
    at Yi's ``copy.75``, ``.80``, ``.84``, k and v ``[1,4096,512]``):
    48 MB rewritten a layer, 7 to 9% of a dense step's device time.
    Held [L, heads * d, hidden] the three copies are gone and the three
    slices into fast memory stay, each product waiting for its own;
    held [L, heads, d, hidden] (``LLMEngine._hold_out_major``) XLA
    slices inside the ``qkv/dot_general`` fusions too, as it always did
    for ``wo``, ``gate``, ``up`` and ``down``.  (The parent's layout
    gets the copy at hidden 256 too, ``bf16[1,256,1024]{1,2,0}``; the
    real widths are where it cost 40 us a layer and where it is held
    off.)"""
    hidden, ffn = REAL_WIDTHS[model]
    text, _ = _dense_step_text(one_chip, monkeypatch, model, tq, False,
                               hidden, ffn)
    _, kvh, d = WIDTHS[model]
    moved, slices = _moved_in_the_layer_loop(text, hidden * kvh * d)
    assert not moved, moved[0][:300]
    assert not slices, slices[0][:300]


# ---------------------------------------------------------------------------
# the indexed and windowed latent kernels (PR 39), at dots3-note-prev-ep8's
# sizes: 128 heads on a 640-wide row behind a 64-head indexer, 64 heads on
# a 1152-wide row under a window of 513, the [33, 2048] table of
# 32,768-token rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tq", [32, 576])
@pytest.mark.parametrize("what", ["window", "selected", "index"])
def test_the_dots3_kernels_compile_for_the_v5e(one_chip, compiled_kernels,
                                               no_persistent_cache, what,
                                               tq):
    """What the chip's compiler refuses here it refuses on the chip: a
    copy from a [rows, 1] array (the index heads' weights ride a row of
    lanes for that), a tile of the selection's bias that starts inside
    a tile of rows (the bias is item-major for that)."""
    from paddle_tpu.ops.pallas import mla_attention as mla

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    row = (sds((33, 2048), jnp.int32), sds((33,), jnp.int32),
           sds((32,), jnp.int32))
    launch = (33, 2048, 32769)
    if what == "window":
        assert mla.ineligible(64, 1152, 1024, 16, launch=launch) is None
        fn = lambda q, pool, bt, cu, kvl: \
            mla.ragged_latent_attention_packed(
                q, pool, 2, bt, cu, kvl, latent_dim=1024, sm_scale=0.0625,
                window=513)
        args = (sds((tq, 64, 1088)), sds((3, 2113, 16, 1152))) + row
        name, out = mla.WINDOW_KERNEL_NAME, r"bf16\[\d+,1024\]"
    elif what == "selected":
        assert mla.ineligible(128, 640, 512, 16, launch=launch,
                              index_dim=128) is None
        fn = lambda q, pool, sel, bt, cu, kvl: \
            mla.ragged_latent_attention_packed(
                q, pool, 1, bt, cu, kvl, latent_dim=512, sm_scale=0.072,
                select=sel)
        args = (sds((tq, 128, 576)), sds((2, 32769, 16, 640)),
                sds((tq, 32768), jnp.float32)) + row
        name, out = mla.SELECT_KERNEL_NAME, r"bf16\[\d+,512\]"
    else:
        fn = lambda q, w, pool, bt, cu, kvl: \
            mla.ragged_index_scores_packed(q, w, pool, 1, bt, cu, kvl)
        args = (sds((tq, 64, 128)), sds((tq, 64), jnp.float32),
                sds((2, 32769, 16, 128))) + row
        name, out = mla.INDEX_KERNEL_NAME, r"f32\[\d+,32768\]"
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert name in text
    assert re.search(out + r"[^\n]* custom-call\(", text)


def test_the_selection_compiles_without_a_sort(one_chip,
                                               no_persistent_cache):
    """The exact top-2048 of 32,768 scores a query, by counting: no sort
    and no top-k custom call in the program."""
    from paddle_tpu.ops.pallas import mla_attention as mla
    args = (jax.ShapeDtypeStruct((576, 32768), jnp.float32,
                                 sharding=one_chip),
            jax.ShapeDtypeStruct((576,), jnp.int32, sharding=one_chip))
    text = jax.jit(lambda s, rel: mla.select_bias(s, rel, 2048)).lower(
        *args).compile().as_text()
    assert " sort(" not in text and "TopK" not in text
    assert " while(" in text


def test_the_three_pool_step_leaves_the_pools_where_they_lie(
        one_chip, compiled_kernels, no_persistent_cache, monkeypatch):
    """The step program of a decoder with indexed full layers and
    windowed latent layers, at dots3-note-prev's attention widths round
    a narrow model: nothing shaped as one layer of one of its
    three arrays, or the whole of one, is made but by the scatters of
    ``kv_write`` (seven: latents and index keys of two full layers, the
    latents of three window layers), every kernel takes the array of ALL
    its layers, and each launch has its name."""
    import dataclasses

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.models.dots3 import Dots3Config, Dots3ForCausalLM
    from paddle_tpu.ops.pallas import mla_attention as mla
    cfg = dataclasses.replace(
        Dots3Config(), vocab_size=1000, hidden_size=256,
        intermediate_size=512, moe_intermediate_size=64, num_hidden_layers=5,
        n_routed_experts=8, experts_held=8, max_position_embeddings=4096)
    eng = LLMEngine(Dots3ForCausalLM(cfg, dtype="bfloat16"),
                    max_num_seqs=ROWS, block_size=BLOCK, num_blocks=4097,
                    max_model_len=4096, max_prefill_tokens=192,
                    prefill_token_bucket=64, enable_prefix_caching=False)
    monkeypatch.setattr(eng, "_platform", "tpu")
    eng.attention_path = eng._resolve_attention_path()
    assert eng.attention_path == "pallas"
    pools = [p.shape for p in eng._pools()]
    assert pools == [(2, 4097, 16, 640), (2, 4097, 16, 128),
                     (3, eng._window_blocks, 16, 1152)]
    tq = 192
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        eng._ragged_arg_structs(tq))
    fn, donate = eng._make_ragged_fn(tq)
    text = jax.jit(fn, donate_argnums=donate).lower(
        *args).compile().as_text()
    _, comps = _computations(text)
    # an array of a pool's shape, or of one layer of it (the queries of
    # 192 tokens and 128 heads are larger than a layer of this test's
    # index keys: the shape is what tells a copy of a pool)
    shaped = {s for p in pools for s in (p, p[1:], (1,) + p[1:])}

    def writes_rows(line):
        # (a layer is an inner jit: its op_names start at the scope)
        return " scatter(" in line and "kv_write/" in line

    large = []
    for lines in comps.values():
        for line in lines:
            m = _RESULT.match(line)
            if m and tuple(int(n) for n in m.group(2).split(",")
                           if n) in shaped:
                large.append((m.group(3), line))
    moved = [line for op, line in large
             if op not in ("parameter", "get-tuple-element", "bitcast")
             and not writes_rows(line)
             and not (op == "fusion" and any(
                 writes_rows(l) for c in _CALLED.findall(line)
                 for l in comps[c]))]
    assert not moved, moved[0][:300]
    scatters = [line for lines in comps.values() for line in lines
                if writes_rows(line)]
    assert len(scatters) == 2 * 2 + 3
    calls = [line for lines in comps.values() for line in lines
             if " custom-call(" in line]
    for name, n, pool in ((mla.SELECT_KERNEL_NAME, 2, pools[0]),
                          (mla.INDEX_KERNEL_NAME, 2, pools[1]),
                          (mla.WINDOW_KERNEL_NAME, 3, pools[2])):
        mine = [c for c in calls if f"{name}" in c.split(" = ")[0]
                or f'"{name}"' in c or f"/{name}/" in c]
        assert len(mine) == n, (name, len(mine))
        stacked = "bf16[{}]".format(",".join(map(str, pool)))
        assert all(stacked in c for c in mine), name


# ---------------------------------------------------------------------------
# the state-space hybrid (PR 42), at Phi-4-mini-flash's sizes: d_inner
# 5120 with 16 states, 40 query heads of 64 widened over 10 cached heads
# of 128, a window of 512 under 256-token chunks (token buckets 32 to
# 320), each row's batch slot a [32] vector beside the two tables
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tq", [32, 320])
def test_the_selective_scan_compiles_for_the_v5e(one_chip, compiled_kernels,
                                                 no_persistent_cache, tq):
    """The ragged selective scan at the real widths, the state of nine
    layers and 33 slots read and written IN PLACE (the aliased operand
    comes back as the second result, and nothing else has its shape)."""
    from paddle_tpu.ops.pallas import selective_scan as ss
    di, n = 5120, 16

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    args = (sds((tq, di)), sds((tq, di)), sds((n, di)), sds((tq, n)),
            sds((tq, n)), sds((di,)), sds((9, ROWS + 1, n, di)),
            sds((), jnp.int32), sds((ROWS,), jnp.int32),
            sds((ROWS + 1,), jnp.int32), sds((ROWS,), jnp.bool_))
    text = jax.jit(lambda *a: ss.selective_scan(*a, use_kernel=True),
                   donate_argnums=(6,)).lower(*args).compile().as_text()
    call, = [l for l in text.splitlines() if " custom-call(" in l
             and ss.KERNEL_NAME in l]
    assert f"f32[{tq},{di}]" in call and f"f32[9,{ROWS + 1},{n},{di}]" in call
    state = [l for l in text.splitlines()
             if re.search(rf"= \(?f32\[9,{ROWS + 1},{n},{di}\]", l)
             and " parameter(" not in l and " custom-call(" not in l
             and " get-tuple-element(" not in l and " bitcast(" not in l]
    assert not state, state[0][:300]


def _phi4flash_step(chip, monkeypatch, tq, layers=12, vocab=1000):
    """(compiled step program, the shapes of its six pools) of the
    state-space hybrid at Phi-4-mini-flash's REAL widths (hidden 2560,
    FFN 10240, d_inner 5120, 40 / 20 heads of 64, window 512) and the
    benchmark's pools, from shapes alone: the engine is a tiny one told
    the real configuration (its builder reads nothing else of the
    model), the parameters are the real model's ShapeDtypeStructs.
    Twelve layers unless told: (Mamba, window) x 3, Mamba, full,
    (memory unit, cross) x 2, every kind and both scans."""
    import dataclasses

    from paddle_tpu.inference import LLMEngine
    from paddle_tpu.inference.sampling import samp_structs
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)
    real = dataclasses.replace(
        Phi4FlashConfig(), num_hidden_layers=layers, vocab_size=vocab,
        max_position_embeddings=8192)
    eng = LLMEngine(
        Phi4FlashForCausalLM(Phi4FlashConfig.tiny(layers=layers, seq=8192),
                             dtype="bfloat16"),
        max_num_seqs=ROWS, block_size=BLOCK, num_blocks=1025,
        max_model_len=8192, max_prefill_tokens=256, prefill_token_bucket=64,
        enable_prefix_caching=False)
    monkeypatch.setattr(eng, "config", real)
    monkeypatch.setattr(eng, "_attn", real.attention_by_kind())
    monkeypatch.setattr(eng, "_kvh", real.page_shape()[0])
    monkeypatch.setattr(eng, "_hd", real.page_shape()[1])
    monkeypatch.setattr(eng, "_window", real.sliding_window)
    monkeypatch.setattr(eng, "_platform", "tpu")
    eng.attention_path = eng._resolve_attention_path()
    assert eng.attention_path == "pallas"

    def sds(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    params = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype), Phi4FlashForCausalLM(
            real, dtype="bfloat16", materialize=False).decode_params())
    page = (10, BLOCK, 128)
    conv, scan = real.state_shapes(ROWS + 1)
    pools = [(1, 16385) + page] * 2 + [(layers // 4, 1569) + page] * 2 \
        + [conv, scan]
    i32 = jnp.int32
    samp = jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype),
                                  samp_structs(eng._Lq, vocab))
    args = (params,) + tuple(sds(p) for p in pools[:5]) \
        + (sds(pools[5], jnp.float32),) + (
        sds((ROWS,), i32),                               # batch slots
        sds((tq,), i32), sds((ROWS + 1,), i32), sds((ROWS,), i32),
        sds((2, ROWS + 1, 512), i32), sds((eng._Lq,), i32), samp,
        sds((eng._Lq,), i32), sds((tq,), i32))           # prev, src
    fn, donate = eng._make_ragged_fn(tq)
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile(), pools


@pytest.mark.parametrize("tq", [32, 320])
def test_the_state_space_step_leaves_pools_and_state_where_they_lie(
        one_chip, compiled_kernels, no_persistent_cache, monkeypatch, tq):
    """The step program of the state-space hybrid at the real widths
    compiles for the v5e (Mosaic takes the scan beside three attention
    launches), and nothing shaped as one of its page pools or as the
    scan's state, or as one layer of either, is made but by the
    ``kv_write`` scatters, the kernels (the state comes back from the
    scan's launch, aliased) and the moves into and out of fast memory
    that the compiler schedules itself round a loop (``copy-start`` /
    ``copy-done``, ``slice-start`` / ``slice-done``: at this test's
    twelve layers the state is 43 MB and the compiler keeps it in fast
    memory; at the model's 32 it is 97 MB, stays in HBM, and the
    compiled program, 10.02 GB of arguments and 0.11 GB of temporaries,
    has none of these: PERF.md section 6, PR 42).  The convolution's
    tails (9 MB) the compiler does move into fast memory and back, at
    any depth, and they are not held to this.  Every launch has
    its name and takes the array of ALL its layers: the full layer's
    and the cross layers' read the ONE layer's pool."""
    from paddle_tpu.inference import layer_stack as ls
    from paddle_tpu.ops.pallas import selective_scan as ss
    compiled, pools = _phi4flash_step(one_chip, monkeypatch, tq)
    text = compiled.as_text()
    _, comps = _computations(text)
    big = [pools[0], pools[2], pools[5]]
    shaped = {s for p in big for s in (p, p[1:], (1,) + p[1:])}

    def writes_rows(line):
        return " scatter(" in line and "kv_write/" in line

    large = []
    for lines in comps.values():
        for line in lines:
            m = _RESULT.match(line)
            if m and tuple(int(n) for n in m.group(2).split(",")
                           if n) in shaped:
                large.append((m.group(3), line))
    moved = [line for op, line in large
             if op not in ("parameter", "get-tuple-element", "bitcast",
                           "custom-call", "copy-start", "copy-done",
                           "slice-start", "slice-done")
             and not writes_rows(line)
             and not (op == "fusion" and any(
                 writes_rows(l) for c in _CALLED.findall(line)
                 for l in comps[c]))]
    assert not moved, moved[0][:300]
    calls = [line for lines in comps.values() for line in lines
             if " custom-call(" in line]

    def named(name):
        return [c for c in calls if re.match(
            rf"\s+(?:ROOT\s+)?%?{name}(?:\.\d+)? = ", c)]

    def shape_of(pool, dt="bf16"):
        return "{}[{}]".format(dt, ",".join(map(str, pool)))

    # one traced copy a kind: the window layers' launch and the Mamba
    # layers' scan once in their scan, the cross layers' once in theirs,
    # the full layer's and layer L/2's scan once each
    for name, n, pool, dt in (
            ("ragged_paged_attention", 1, pools[0], "bf16"),
            (pa.WINDOW_KERNEL_NAME, 1, pools[2], "bf16"),
            (ls.CROSS_KERNEL_NAME, 1, pools[0], "bf16"),
            (ss.KERNEL_NAME, 2, pools[5], "f32")):
        mine = named(name)
        assert len(mine) == n, (name, len(mine))
        assert all(shape_of(pool, dt) in c for c in mine), name
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 256 << 20

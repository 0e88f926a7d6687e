"""The scheduler's two programs compiled for the chip, without the chip.

The TPU's compiler is installed here and compiles for a v5e that is
described, not attached: nothing runs, no weight is allocated, and what
comes back is the optimized program the chip would run.  These tests read
it at the sizes of the benchmark's three configurations
(``mistral7b-bge-rag``: 24 layers at published widths; ``mimo-v2.5-bge-rag``
and ``nemotron-3-nano-bge-rag``: one chip's share; 8 slots, 257 pages of 16
tokens) and hold what no CPU compile can show: that a step reads each
weight where it lies, an expert among them, and touches of the KV pools
only what its tables name.  The layouts that matter (which product wants
which operand tiled how) exist on this backend alone.

All of them live in this one file, and the topology is described inside a
fixture: one process at a time may load the TPU's library, so every
worker collects the same tests and only the one given this file loads it.
"""

from __future__ import annotations

import json
import os
import re
import types

import jax
import numpy as np
import pytest

from pathway_tpu.models import decoder as dec
from pathway_tpu.serving.generation import GenerationScheduler
from tests.decoder_oracle import (
    HLO_INSTRUCTION, aliased_parameters, elements, lower_program,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGES, TABLE_WIDTH, PREFILL_WIDTH = 257, 32, 512


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled(one_chip, config: str):
    """``(cfg, tree shapes, k_pool shapes, {"decode": text, "prefill":
    text})`` of the scheduler's two programs for a configuration the
    benchmark serves, compiled for the described chip."""
    with open(os.path.join(ROOT, "chipbench", "configs", f"{config}.json")) as f:
        hf = {k: v for k, v in json.load(f).items() if k != "chipbench"}
    cfg = dec.decoder_config_from_hf(hf)

    def array(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def on_chip(tree):
        return jax.tree_util.tree_map(lambda x: array(x.shape, x.dtype), tree)

    # the scheduler as the server builds it, but for its pools (two pages
    # here: the programs are lowered over shapes, not over these arrays)
    lm = types.SimpleNamespace(config=cfg, params=None, max_cache=1024, eos_id=None)
    sched = GenerationScheduler(lm, pages=2)
    cache_was = jax.config.jax_enable_compilation_cache
    # an entry compiled for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        tree = on_chip(jax.eval_shape(lambda: dec.init_decoder_params(cfg, 0)))
        kp, vp = on_chip(jax.eval_shape(
            lambda: dec.init_kv_pool(cfg, PAGES, sched.page_size, sched.slots)
        ))
        decode, prefill = (
            lower_program(
                sched, which, tree, kp, vp, width=PREFILL_WIDTH, rows=1,
                table_width=TABLE_WIDTH, array=array,
            ).compile().as_text()
            for which in ("decode", "prefill")
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        sched.shutdown()
    return cfg, tree, kp, {"decode": decode, "prefill": prefill}


@pytest.fixture(scope="module")
def programs(one_chip):
    """The two programs' texts and the shapes the assertions are about,
    for the Mistral configuration the benchmark serves."""
    cfg, tree, kp, texts = _compiled(one_chip, "mistral7b-bge-rag")
    D, H = cfg.head_dim, cfg.hidden
    return {
        **texts,
        "pool": kp.shape,
        "projections": {H * cfg.heads * D, H * cfg.kv_heads * D},
        "first_pool": len(jax.tree_util.tree_leaves(tree)),
    }


def _scheduled(text: str):
    """``(name, elements, opcode)`` of the instructions the device runs one
    by one: those of the entry and of the loops' bodies, not those inside
    a fusion (a slice there is a read fused into its consumer, which is
    what is wanted)."""
    fused = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    out = []
    for block in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text):
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", block)
        if head is None or head.group(1) in fused:
            continue
        out += [
            (name, elements(dims), op)
            for name, dims, op in HLO_INSTRUCTION.findall(block)
        ]
    return out


def _moves_data_only(name: str, op: str) -> bool:
    """A copy, or a fusion that computes nothing: the compiler names those
    after what they do (``constant_dynamic-slice_fusion.10``,
    ``copy_bitcast_fusion``)."""
    if op in ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice", "broadcast"):
        return True
    return op == "fusion" and re.search(r"slice|copy|broadcast", name) is not None


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_mistral_step_on_the_chip_reads_weights_and_pools_in_place(programs, which):
    text = programs[which]
    assert text.startswith(f"HloModule jit__{which}")
    scheduled = _scheduled(text)
    assert len(scheduled) > 100  # the parse found the loop's body
    pool = programs["pool"]
    whole = int(np.prod(pool))
    sizes = {whole, whole // pool[0]} | programs["projections"]
    moved = [
        (name, op, n) for name, n, op in scheduled
        if n in sizes and _moves_data_only(name, op)
    ]
    assert not moved, moved
    # the pools go in and come out in one buffer, and flow through the
    # layers' loop as they are
    first = programs["first_pool"]
    assert {first, first + 1} <= aliased_parameters(text)


@pytest.fixture(scope="module")
def nemotron_programs(one_chip):
    cfg, tree, kp, texts = _compiled(one_chip, "nemotron-3-nano-bge-rag")
    return {**texts, "cfg": cfg, "tree": tree, "pools": kp}


def _grouped_products(text: str) -> int:
    """The grouped products a compiled program holds: calls of the Pallas
    kernel ``ops/grouped_matmul.py`` (a ``tpu_custom_call`` named
    ``%grouped_matmul.N``), the only grouped product a program lowered
    for the chip has (no ``%ragged-dot-none.N`` is left)."""
    assert not re.search(r"^\s*(?:ROOT )?%ragged-dot", text, flags=re.M)
    return len(re.findall(
        r"^\s*(?:ROOT )?%grouped_matmul[\w.\-]* = .*custom_call_target=\"tpu_custom_call\"",
        text, flags=re.M,
    ))


def _loops(text: str) -> int:
    return len(re.findall(r" while\(", text))


def _moved_whole(scheduled, least: int):
    """The scheduled instructions of ``least`` elements or more that only
    move data."""
    return [
        (name, op, n) for name, n, op in scheduled
        if n >= least and _moves_data_only(name, op)
    ]


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_nemotron_step_on_the_chip_copies_no_expert_and_keeps_state_in_place(
        nemotron_programs, which):
    """Nemotron 3 Nano's share at published widths (16 runs of one layer;
    64 experts of 1,856 columns a routed layer, stored as 1,920).  The
    decode step (8 rows) holds no grouped product: a routed layer loops
    over the experts it met and reads each where it lies, so nothing the
    size of ONE expert is copied and the only loops left are those seven
    (a scan of one step, a run that is one layer long, is inlined).  The
    prefill program (512 rows) keeps its fourteen grouped products, which
    read a layer's experts where they lie (given 1,856 columns the
    compiler padded a copy of all 64, 638 MB a layer a step), and the
    loops that carry the scan's state from chunk to chunk.  In both every
    run's pair of the pools, the Mamba-2 layers' recurrent state among
    them, goes in and comes out in one buffer."""
    text, cfg = nemotron_programs[which], nemotron_programs["cfg"]
    assert text.startswith(f"HloModule jit__{which}")
    scheduled = _scheduled(text)
    assert len(scheduled) > 100
    expert = cfg.hidden * dec._lanes(1856)
    state = int(np.prod(nemotron_programs["pools"][0].shape))  # a run's convolution tails
    if which == "decode":
        assert _grouped_products(text) == 0
        assert _loops(text) == cfg.routed_layers == 7
        least = expert
    else:
        assert _grouped_products(text) == 2 * cfg.routed_layers
        assert _loops(text) == cfg.ssm_layers
        least = cfg.experts * expert
    assert not _moved_whole(scheduled, least)
    assert state == 8 * 3 * 6144
    first = len(jax.tree_util.tree_leaves(nemotron_programs["tree"]))
    carrying = [r for r, (kind, _n) in enumerate(cfg.runs) if kind.part != "ffn"]
    assert len(carrying) == 9  # 7 Mamba-2 runs' state, 2 attention runs' pages
    pairs = {first + r for r in carrying} | {first + len(cfg.runs) + r for r in carrying}
    assert pairs <= aliased_parameters(text)


@pytest.fixture(scope="module")
def laguna_programs(one_chip):
    cfg, tree, kp, texts = _compiled(one_chip, "laguna-s-2.1-bge-rag")
    return {**texts, "cfg": cfg, "tree": tree, "pools": kp}


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_laguna_step_on_the_chip_copies_no_expert_and_keeps_the_pools_donated(
        laguna_programs, which):
    """Laguna-S-2.1's share at published widths (runs full-dense, window x
    3, full; 128 gated experts of 3,072 x 1,024 a routed layer beside a
    shared expert; 72 query heads in window layers, 48 in full ones, each
    with its gate): the decode step holds no grouped product, one loop a
    routed body (the scanned window run's, inside the scan's own loop, and
    the full run's) and no copy the size of one expert; the prefill
    program keeps its three grouped products a routed body and no loop but
    the layer scan, and copies no layer's expert stack.  Both take every
    run's pools in and hand them out in one buffer."""
    text, cfg = laguna_programs[which], laguna_programs["cfg"]
    assert text.startswith(f"HloModule jit__{which}")
    scheduled = _scheduled(text)
    assert len(scheduled) > 100
    scans = sum(1 for _kind, n in cfg.runs if n > 1)
    routed_bodies = sum(1 for kind, _n in cfg.runs if kind.routed)
    assert (scans, routed_bodies, cfg.routed_layers) == (1, 2, 4)
    assert [kind.heads for kind, _n in cfg.runs] == [48, 72, 48]
    expert = cfg.hidden * 1024
    # a window layer's gathered ring, [8 slots, 528, 8, 128], is laid out
    # anew for the scores (PERF.md section 5); nothing a whole number of
    # experts in size moves
    experts_moved = lambda least: [
        moved for moved in _moved_whole(scheduled, least) if moved[2] % expert == 0
    ]
    if which == "decode":
        assert _grouped_products(text) == 0
        assert _loops(text) == scans + routed_bodies
        assert not experts_moved(expert)
    else:
        assert _grouped_products(text) == 3 * routed_bodies
        assert _loops(text) == scans
        assert not experts_moved(cfg.experts * expert)
    first = len(jax.tree_util.tree_leaves(laguna_programs["tree"]))
    runs = len(cfg.runs)
    assert set(range(first, first + 2 * runs)) <= aliased_parameters(text)


@pytest.fixture(scope="module")
def mimo_programs(one_chip):
    cfg, _tree, _kp, texts = _compiled(one_chip, "mimo-v2.5-bge-rag")
    return {**texts, "cfg": cfg}


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_mimo_step_on_the_chip_loops_over_hit_experts_and_prefill_groups(
        mimo_programs, which):
    """MiMo-V2.5's share at published widths (32 gated experts of 4,096 x
    2,048 a routed layer; runs G-dense, W x 4, G, W): the decode step
    holds no grouped product, one loop a routed body (the scanned run's,
    inside the scan's own loop, and the two runs of one layer) and no
    copy the size of one expert; the prefill program keeps its three
    grouped products a routed body and no loop but the layer scan, and
    copies no layer's expert stack."""
    text, cfg = mimo_programs[which], mimo_programs["cfg"]
    assert text.startswith(f"HloModule jit__{which}")
    scheduled = _scheduled(text)
    assert len(scheduled) > 100
    scans = sum(1 for _kind, n in cfg.runs if n > 1)
    routed_bodies = sum(1 for kind, _n in cfg.runs if kind.routed)
    assert (scans, routed_bodies, cfg.routed_layers) == (1, 3, 6)
    expert = cfg.hidden * dec._lanes(2048)
    if which == "decode":
        assert _grouped_products(text) == 0
        assert _loops(text) == scans + routed_bodies
        assert not _moved_whole(scheduled, expert)
    else:
        assert _grouped_products(text) == 3 * routed_bodies
        assert _loops(text) == scans
        assert not _moved_whole(scheduled, cfg.experts * expert)

"""perfbench/segments.py: from a compiled step's text and a trace's events
to device time by the program's own names.

The text here is written by hand in the form ``compiled.as_text()`` has
on the v5e (instruction names, fusions that call fused computations, a
``while`` whose body has events of its own, ``op_name`` metadata); the
recorded excerpt beside the tests (``data/recorded_segments.json``) is cut
from a traced run of the benchmark's cell on the chip.
"""

import json
import os

import pytest

from perfbench import harness, segments

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1_000_000
SEGMENTS = ("seg.embed", "seg.attn_proj", "seg.attn_core", "seg.mlp",
            "seg.head_loss")
KERNELS = ("flash_fwd", "flash_fwd_grouped", "flash_bwd_dq", "flash_bwd_dkv")
NEW_METRICS = [
    "train.seg.attn_proj_ms", "train.seg.attn_core_ms", "train.seg.mlp_ms",
    "train.seg.embed_ms", "train.seg.head_loss_ms", "train.seg.update_ms",
    "train.seg.unattributed_share", "kernel.flash_fwd_ms.train",
    "kernel.flash_dq_ms.train", "kernel.flash_dkv_ms.train"]

FWD = "jit(step)/jvp()/while/body/closed_call/"
BWD = "jit(step)/transpose(jvp())/while/body/closed_call/"
TEXT = f"""HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: bf16[8], p1: f32[8]) -> bf16[8] {{
  %p0 = bf16[8]{{0}} parameter(0)
  %p1 = f32[8]{{0}} parameter(1)
  %dynamic-slice.1 = f32[8]{{0}} dynamic-slice(%p1), metadata={{op_name="jit(step)/jvp()/while/body/dynamic_slice"}}
  %convert.1 = bf16[8]{{0}} convert(%dynamic-slice.1), metadata={{op_name="{FWD}seg.mlp/convert_element_type"}}
  %convolution.1 = bf16[8]{{0}} convolution(%p0, %convert.1), metadata={{op_name="{FWD}seg.mlp/dot_general"}}
  ROOT %add.1 = bf16[8]{{0}} add(%convolution.1, %p0), metadata={{op_name="{FWD}seg.attn_proj/add"}}
}}

%fused_computation.2 (p0: bf16[8]) -> bf16[8] {{
  %p0.1 = bf16[8]{{0}} parameter(0)
  %concatenate.1 = bf16[8]{{0}} concatenate(%p0.1), metadata={{op_name="{FWD}seg.attn_proj/rope/concatenate"}}
  ROOT %transpose.1 = bf16[8]{{0}} transpose(%concatenate.1), metadata={{op_name="{FWD}seg.attn_core/transpose"}}
}}

%fused_computation.3 (p0: f32[8]) -> f32[8] {{
  %p0.2 = f32[8]{{0}} parameter(0)
  %multiply.1 = f32[8]{{0}} multiply(%p0.2, %p0.2), metadata={{op_name="{BWD}seg.mlp/norm/mul"}}
  ROOT %add.2 = f32[8]{{0}} add(%multiply.1, %p0.2), metadata={{op_name="{BWD}seg.mlp/seg.attn_core/add_any"}}
}}

%fused_computation.4 (p0: f32[8]) -> f32[8] {{
  %p0.3 = f32[8]{{0}} parameter(0)
  %sqrt.1 = f32[8]{{0}} sqrt(%p0.3), metadata={{op_name="jit(step)/sqrt"}}
  ROOT %divide.1 = f32[8]{{0}} divide(%p0.3, %sqrt.1), metadata={{op_name="jit(step)/div"}}
}}

%fused_computation.5 (p0: bf16[8], p1: f32[8]) -> f32[8] {{
  %p0.4 = bf16[8]{{0}} parameter(0)
  %p1.4 = f32[8]{{0}} parameter(1)
  %convert.2 = f32[8]{{0}} convert(%p0.4), metadata={{op_name="jit(step)/transpose(jvp(seg.embed))/convert_element_type"}}
  %sqrt.2 = f32[8]{{0}} sqrt(%p1.4), metadata={{op_name="jit(step)/sqrt"}}
  ROOT %divide.2 = f32[8]{{0}} divide(%convert.2, %sqrt.2), metadata={{op_name="jit(step)/div"}}
}}

%fused_computation.6 (p0: f32[8]) -> f32[8] {{
  %p0.5 = f32[8]{{0}} parameter(0)
  ROOT %scatter.1 = f32[8]{{0}} scatter(%p0.5), metadata={{op_name="scatter-add"}}
}}

%body (param: (s32[], bf16[8])) -> (s32[], bf16[8]) {{
  %param = (s32[]{{:T(128)}}, bf16[8]{{0:T(8,128)(2,1)}}) parameter(0)
  %get-tuple-element.5 = bf16[8]{{0}} get-tuple-element(%param), index=1
  %get-tuple-element.6 = s32[] get-tuple-element(%param), index=0
  %fusion.1 = bf16[8]{{0}} fusion(%get-tuple-element.5, %get-tuple-element.5), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{FWD}seg.attn_proj/add"}}
  %fusion.2 = bf16[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={{op_name="{FWD}seg.attn_core/transpose"}}
  %copy.92 = bf16[8]{{0}} copy(%fusion.2)
  %flash_fwd.6 = (bf16[8]{{0}}, f32[8]{{0}}) custom-call(%copy.92), custom_call_target="tpu_custom_call", metadata={{op_name="{FWD}seg.attn_core/flash_fwd/flash_fwd/pallas_call"}}
  %flash_bwd_dq.12 = bf16[8]{{0}} custom-call(%copy.92), custom_call_target="tpu_custom_call", metadata={{op_name="{BWD}seg.attn_core/flash_bwd_dq/flash_bwd_dq/pallas_call"}}
  %fusion.3 = f32[8]{{0}} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{BWD}seg.mlp/norm/mul"}}
  ROOT %tuple.1 = (s32[]{{:T(128)}}, bf16[8]{{0:T(8,128)(2,1)}}) tuple(%get-tuple-element.6, %get-tuple-element.5)
}}

%cond (param.1: (s32[], bf16[8])) -> pred[] {{
  %param.1 = (s32[], bf16[8]{{0}}) parameter(0)
  ROOT %compare.1 = pred[] compare(%param.1, %param.1), direction=LT, metadata={{op_name="jit(step)/jvp()/while/cond/lt"}}
}}

ENTRY %main.47 (params.1: f32[2,8]) -> f32[8] {{
  %params.1 = f32[2,8]{{1,0}} parameter(0)
  %constant.1 = s32[] constant(0)
  %convert.65 = bf16[2,8]{{1,0}} convert(%params.1), backend_config={{"flag_configs":[]}}
  %tuple.2 = (s32[]{{:T(128)}}, /*index=1*/bf16[8]{{0:T(8,128)(2,1)}}) tuple(%constant.1, %convert.65)
  %while.3 = (s32[]{{:T(128)}}, bf16[8]{{0:T(8,128)(2,1)}}) while(%tuple.2), condition=%cond, body=%body, metadata={{op_name="jit(step)/jvp()/while"}}
  %get-tuple-element.7 = bf16[8]{{0}} get-tuple-element(%while.3), index=1
  %fusion.5 = f32[8]{{0}} fusion(%get-tuple-element.7, %params.1), kind=kLoop, calls=%fused_computation.5, metadata={{op_name="jit(step)/div"}}
  %broadcast.7 = f32[8]{{0}} broadcast(%constant.1), dimensions={{}}
  %fusion.6 = f32[8]{{0}} fusion(%broadcast.7), kind=kLoop, calls=%fused_computation.6, metadata={{op_name="scatter-add"}}
  %copy.9 = f32[8]{{0}} copy(%fusion.5)
  ROOT %fusion.4 = f32[8]{{0}} fusion(%fusion.6, %copy.9), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="jit(step)/div"}}
}}
"""


def _table(text=TEXT):
    return segments.attribute(text, SEGMENTS, KERNELS)


def _segment(table, name):
    return table[name]["segment"], table[name]["way"]


def test_the_outermost_segment_on_a_path_decides():
    seg = lambda *paths: segments.classify(
        [(p, False) for p in paths], SEGMENTS)
    assert seg(FWD + "seg.mlp/norm/mul") == ("seg.mlp", "forward")
    assert seg(BWD + "seg.mlp/seg.attn_core/add_any") == (
        "seg.mlp", "backward")
    assert seg("jit(step)/jvp(seg.head_loss)/seg.head_loss/dot_general",
               "jit(step)/transpose(jvp(seg.head_loss))/mul") == (
        "seg.head_loss", "both")
    # named, no segment, outside the differentiated function: the update
    assert seg("jit(step)/sqrt", "jit(step)/div") == (segments.UPDATE, "")
    # the loop's plumbing follows what it is fused with; alone, the update
    plumbing = "jit(step)/jvp()/while/body/dynamic_slice"
    assert seg(plumbing, FWD + "seg.mlp/mul") == ("seg.mlp", "forward")
    assert seg(plumbing) == (segments.UPDATE, "")
    # no name at all: nobody's
    assert seg() == (segments.UNATTRIBUTED, "")
    # a longer name that only starts like a segment is not that segment
    assert seg(FWD + "seg.mlp_extra/mul") == (segments.UPDATE, "")


def test_a_fusion_of_two_classes_goes_to_its_matmul_or_to_nobody():
    table = _table()
    # fusion.1: attn_proj's add at the root, mlp's matmul inside, and a
    # dynamic slice with no segment: the matmul decides
    assert table["fusion.1"] == {"segment": "seg.mlp", "way": "forward",
                                 "kernel": None, "named": True}
    # fusion.2: rope's concatenate and attn_core's transpose, no matmul
    assert _segment(table, "fusion.2") == (segments.UNATTRIBUTED, "")
    # fusion.3: both operations have seg.mlp outermost
    assert _segment(table, "fusion.3") == ("seg.mlp", "backward")
    assert _segment(table, "fusion.4") == (segments.UPDATE, "")
    # fusion.5: the optimizer's operations and a cast of embed's gradient
    assert _segment(table, "fusion.5") == (segments.UNATTRIBUTED, "")
    # fused instructions have no event of their own: not in the table
    assert "convolution.1" not in table and "sqrt.1" not in table


def test_a_nameless_instruction_takes_the_segment_of_its_consumers():
    table = _table()
    # the hoisted cast: through the tuple, into the loop's body by its
    # index, to the matmul's fusion; the body's root hands it on to
    # fusion.5, which is nobody's and so has no say
    assert _segment(table, "convert.65") == ("seg.mlp", "forward")
    # one hop, to the two kernels
    assert _segment(table, "copy.92") == ("seg.attn_core", "both")
    # a bare primitive's name is no name: fusion.6 and the zeros it
    # scatters into go where fusion.6's result goes
    assert _segment(table, "fusion.6") == (segments.UPDATE, "")
    assert _segment(table, "broadcast.7") == (segments.UPDATE, "")
    assert _segment(table, "copy.9") == (segments.UPDATE, "")
    # consumers of two segments: nobody's
    both = TEXT.replace("custom-call(%copy.92), custom_call_target="
                        f'"tpu_custom_call", metadata={{op_name="{BWD}'
                        "seg.attn_core", "custom-call(%copy.92), "
                        'custom_call_target="tpu_custom_call", metadata='
                        f'{{op_name="{BWD}seg.mlp')
    assert both != TEXT
    assert _segment(_table(both), "copy.92") == (segments.UNATTRIBUTED, "")


def test_the_kernels_are_found_by_their_names():
    table = _table()
    assert table["flash_fwd.6"]["kernel"] == "flash_fwd"
    assert table["flash_bwd_dq.12"]["kernel"] == "flash_bwd_dq"
    assert table["flash_bwd_dq.12"]["way"] == "backward"
    assert [n for n, r in table.items() if r["kernel"]] == [
        "flash_fwd.6", "flash_bwd_dq.12"]


def _events():
    text = lambda name: f"%{name} = bf16[8]{{0}} fusion(...), kind=kLoop"
    return {"/device:TPU:0": [
        (text("convert.65"), 0, 2 * MS),            # seg.mlp, inherited
        (text("while.3"), 2 * MS, 20 * MS),         # 1 ms of its own
        (text("fusion.1"), 2 * MS, 6 * MS),         # seg.mlp
        (text("fusion.2"), 8 * MS, 2 * MS),         # nobody's
        (text("flash_fwd.6"), 10 * MS, 4 * MS),     # seg.attn_core
        (text("flash_bwd_dq.12"), 14 * MS, 4 * MS),
        (text("fusion.3"), 18 * MS, 3 * MS),        # seg.mlp
        (text("fusion.4"), 22 * MS, 9 * MS),        # update
        (text("fusion.777"), 31 * MS, 1 * MS)]}     # not in the text


def test_self_time_under_a_while_and_the_sum_is_the_busy_time():
    got = segments.reduce(_events(), _table(), steps=2)
    ms = {k: v * 1e3 for k, v in got["segment"].items()}
    # two steps: every figure is half the events' time. The while keeps
    # the one millisecond no operation of its body covers, as the update.
    assert ms == pytest.approx({
        "seg.mlp": 5.5, "seg.attn_core": 4.0, segments.UPDATE: 5.0,
        segments.UNATTRIBUTED: 1.5})
    assert sum(ms.values()) == pytest.approx(got["busy_s"] * 1e3) == 16.0
    assert got["kernel"] == pytest.approx(
        {"flash_fwd": 2e-3, "flash_bwd_dq": 2e-3})
    assert got["way"][("seg.mlp", "forward")] == pytest.approx(4e-3)
    assert got["way"][("seg.mlp", "backward")] == pytest.approx(1.5e-3)


def _ctx(table_text, monkeypatch, calls=None):
    monkeypatch.setattr(segments, "vocabulary", lambda: (SEGMENTS, KERNELS))

    def text(_cell):
        if calls is not None:
            calls.append(1)
        return table_text
    monkeypatch.setattr(segments, "compiled_text", text)
    return {"cell": {}, "traced_steps": 2,
            "planes": {p: {"XLA Ops": ev} for p, ev in _events().items()}}


def test_an_instruction_the_text_lacks_is_unattributed(monkeypatch):
    ctx = _ctx(TEXT, monkeypatch)
    # fusion.2 and fusion.777: 3 of the 32 ms
    assert segments.unattributed_share(ctx) == pytest.approx(9.375)
    assert segments.segment_ms(ctx, "seg.mlp") == pytest.approx(5.5)
    assert segments.segment_ms(ctx, "seg.embed") == 0.0
    assert segments.kernel_ms(ctx, "flash_fwd") == pytest.approx(2.0)
    assert segments.kernel_ms(ctx, "flash_bwd_dkv") is None


@pytest.mark.parametrize("stale", [
    TEXT.replace("seg.", "scope."),            # another prefix: no names
    TEXT.replace("seg.attn_proj", "seg.attention"),   # one other name
    "HloModule jit_step\n"])                   # nothing at all
def test_another_commits_names_read_as_unattributed(monkeypatch, stale):
    """The cache hazard: a text whose names are not this vocabulary's gives
    no segment anything, so the share reads 100 and not a wrong segment."""
    ctx = _ctx(stale, monkeypatch)
    assert segments.unattributed_share(ctx) == pytest.approx(100.0)
    for s in SEGMENTS + (segments.UPDATE,):
        assert segments.segment_ms(ctx, s) == 0.0
    assert segments.kernel_ms(ctx, "flash_fwd") is None


def _readers():
    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    return manifest, {m: harness.reader(manifest["paths"], m)
                      for m in NEW_METRICS}


def test_ten_readers_share_one_compile(monkeypatch):
    calls = []
    ctx = _ctx(TEXT, monkeypatch, calls)
    _manifest, readers = _readers()
    got = {m: read(ctx) for m, read in readers.items()}
    assert len(calls) == 1
    assert got["train.seg.mlp_ms"] == pytest.approx(5.5)
    assert got["train.seg.update_ms"] == pytest.approx(5.0)
    assert got["train.seg.unattributed_share"] == pytest.approx(9.375)
    assert got["kernel.flash_dq_ms.train"] == pytest.approx(2.0)
    assert got["kernel.flash_dkv_ms.train"] is None


def test_a_program_without_the_names_gives_nothing(monkeypatch):
    """The parent of the PR that brought the names: every reader returns
    None and nothing is compiled."""
    calls = []
    ctx = _ctx(TEXT, monkeypatch, calls)
    monkeypatch.setattr(segments, "vocabulary", lambda: None)
    _manifest, readers = _readers()
    assert [read(ctx) for read in readers.values()] == [None] * 10
    assert not calls
    assert [read({}) for read in readers.values()] == [None] * 10


def test_every_new_entry_has_its_reader_and_every_reader_its_entry():
    manifest, _readers_ = _readers()
    entries = {m["name"]: m for m in manifest["per_layer"]}
    files = {f[:-3] for f in os.listdir(
        os.path.join(ROOT, "perfbench", "layer_metrics")) if f.endswith(".py")}
    assert files == set(entries)
    for name in NEW_METRICS:
        e = entries[name]
        assert (e["source"], e["moves"], e["better"]) == (
            "device_trace", "train_tokens_per_s", "lower")
        assert e["workloads"] == ["mistral7b-train.seq4k"]
        assert e["unit"] == ("%" if name.endswith("share") else "ms")
        assert e["layer"] == ("kernels" if name.startswith("kernel.")
                              else "train step")
    # new entries stand at the end, behind the four the cell had
    assert [m["name"] for m in manifest["per_layer"]][4:] == NEW_METRICS


def test_the_vocabulary_is_the_programs():
    from ray_tpu.util import profiling

    assert segments.vocabulary() == (profiling.SEGMENTS, profiling.KERNELS)
    assert (SEGMENTS, KERNELS) == (profiling.SEGMENTS, profiling.KERNELS)


# -------------------------------------------------- a recorded chip trace
with open(os.path.join(HERE, "data", "recorded_segments.json")) as _f:
    RECORDED = json.load(_f)

# Read by hand from the recorded text's own op_names: the step's largest
# instructions and the compiler's nameless ones, with what each is.
BY_HAND = {
    "fusion.200": "seg.head_loss",    # lm_head's gradient matmul, fused
                                      # with that leaf's AdamW update
    "fusion.282": "seg.head_loss",    # the lm_head matmul, forward
    "fusion.221": "seg.head_loss",
    "reshape.703": "seg.head_loss",   # take_along_axis' scatter, backward
    "fusion.405": "seg.mlp", "fusion.374": "seg.mlp", "fusion.373": "seg.mlp",
    "bitcast_add_fusion.3": "seg.mlp", "fusion.404": "seg.mlp",
    "fusion.403": "seg.mlp", "bitcast_dynamic-update-slice_fusion.41":
    "seg.mlp",                        # a weight's gradient matmul, written
                                      # into the stacked gradient
    "fusion.410": "seg.attn_proj", "fusion.367": "seg.attn_proj",
    "fusion.372": "seg.attn_proj", "fusion.407": "seg.attn_proj",
    "bitcast_dynamic-update-slice_fusion.44": "seg.attn_proj",
    "bitcast_dynamic-update-slice_fusion.43": "seg.attn_proj",
    "convert.65": "seg.mlp",          # w_down's cast, hoisted, nameless
    "convert.66": "seg.mlp", "convert.67": "seg.mlp",     # w_gate, w_up
    "convert.69": "seg.attn_proj",    # wo's
    "convert.70": "seg.attn_proj",    # wq's
    "flash_fwd.6": "seg.attn_core", "flash_bwd_dq.12": "seg.attn_core",
    "flash_bwd_dkv.12": "seg.attn_core",
    "fusion.2": "seg.embed",          # the scatter-add into the table
    "fusion.206": segments.UPDATE, "fusion.204": segments.UPDATE,
    "fusion.208": segments.UPDATE,    # AdamW over the three MLP leaves
    "fusion.378": "seg.mlp",          # the loop keeping a cast weight slice
                                      # for the backward pass: plumbing,
                                      # alone, so its consumers' segment
    "fusion.211": segments.UPDATE,    # AdamW over wq
    "fusion.202": segments.UNATTRIBUTED,  # the table's AdamW update fused
                                          # with the cast of its gradient
    "fusion.369": segments.UNATTRIBUTED,  # rope's concatenate fused with
                                          # attn_core's reshape
}


def _recorded():
    table = segments.attribute("\n".join(RECORDED["text"]), SEGMENTS, KERNELS)
    events = [tuple(e) for e in RECORDED["events"]]
    return table, events


def test_recorded_step_each_instruction_is_what_it_reads_as_by_hand():
    table, _events = _recorded()
    assert {n: table[n]["segment"] for n in BY_HAND} == BY_HAND
    assert table["fusion.200"]["way"] == "backward"
    assert table["convert.65"]["way"] == "both"     # both loops read it


def test_recorded_step_sums_to_its_busy_time():
    table, events = _recorded()
    got = segments.reduce({"/device:TPU:0": events}, table, steps=1)
    ns = {k: round(v * 1e9) for k, v in got["segment"].items()}
    # The two whiles are 30,617,468 and 51,486,662 ns long and hold their
    # bodies' events; every other event stands alone. So the sum of all
    # durations less the whiles' own counts nothing twice:
    whiles = sum(d for n, _s, d in events if n.startswith("%while."))
    alone = sum(d for n, _s, d in events if not n.startswith("%while."))
    assert whiles == 30_617_468 + 51_486_662
    busy = round(got["busy_s"] * 1e9)
    assert busy == 142_116_306
    assert sum(ns.values()) == busy
    # what the whiles keep for themselves is the update's
    inside = sum(d for n, s, d in events if not n.startswith("%while.")
                 and any(ws <= s and s + d <= ws + wd
                         for wn, ws, wd in events if wn.startswith("%while.")))
    assert 0 <= whiles - inside < 100_000
    assert busy == alone + whiles - inside
    # the hand-read instructions, summed from the events one by one, are
    # most of each segment and never more than it
    for segment in set(BY_HAND.values()):
        by_hand = sum(d for n, _s, d in events
                      if BY_HAND.get(n.split(" = ")[0].lstrip("%")) == segment)
        assert 0.4 * ns[segment] < by_hand <= ns[segment], segment
    assert ns == {"seg.mlp": 58_721_234, "seg.head_loss": 26_560_245,
                  segments.UPDATE: 18_103_848, "seg.attn_proj": 16_860_691,
                  "seg.attn_core": 12_972_057, "seg.embed": 3_480_730,
                  segments.UNATTRIBUTED: 5_417_501}
    assert {k: round(v * 1e9) for k, v in got["kernel"].items()} == {
        "flash_fwd": 3_147_571, "flash_bwd_dq": 2_870_774,
        "flash_bwd_dkv": 4_974_277}

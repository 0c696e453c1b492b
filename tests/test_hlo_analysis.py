"""Collective accounting over compiled HLO text
(repro.launch.hlo_analysis.parse_collectives)."""

from repro.launch.hlo_analysis import parse_collectives

# a 4-trip while whose body gathers 16 floats every trip and holds a
# conditional whose taken-or-not branch reduces 8 floats; the entry
# gathers 4 more floats once, inside a called computation
HLO = """\
HloModule jit_f, entry_computation_layout={(f32[8]{0}, pred[])->f32[8]{0}}

%add (x.0: f32[], y.0: f32[]) -> f32[] {
  %x.0 = f32[] parameter(0)
  %y.0 = f32[] parameter(1)
  ROOT %sum.0 = f32[] add(%x.0, %y.0)
}

%keep (a.1: (f32[8])) -> (f32[8]) {
  %a.1 = (f32[8]{0}) parameter(0)
  %g.1 = f32[8]{0} get-tuple-element(%a.1), index=0
  ROOT %t.1 = (f32[8]{0}) tuple(%g.1)
}

%reduce (a.2: (f32[8])) -> (f32[8]) {
  %a.2 = (f32[8]{0}) parameter(0)
  %g.2 = f32[8]{0} get-tuple-element(%a.2), index=0
  %all-reduce.3 = f32[8]{0} all-reduce(%g.2), replica_groups={{0,1}}, to_apply=%add
  ROOT %t.2 = (f32[8]{0}) tuple(%all-reduce.3)
}

%body (b.0: (f32[8], pred[])) -> (f32[8], pred[]) {
  %b.0 = (f32[8]{0}, pred[]) parameter(0)
  %g.3 = f32[8]{0} get-tuple-element(%b.0), index=0
  %g.4 = pred[] get-tuple-element(%b.0), index=1
  %all-gather.5 = f32[16]{0} all-gather(%g.3), replica_groups={{0,1}}, dimensions={0}
  %tuple.6 = (f32[8]{0}) tuple(%g.3)
  %conditional.7 = (f32[8]) conditional(%g.4, %tuple.6, %tuple.6), branch_computations={%keep, %reduce}
  %g.8 = f32[8]{0} get-tuple-element(%conditional.7), index=0
  ROOT %tuple.9 = (f32[8]{0}, pred[]) tuple(%g.8, %g.4)
}

%cond (c.0: (f32[8], pred[])) -> pred[] {
  %c.0 = (f32[8]{0}, pred[]) parameter(0)
  ROOT %g.6 = pred[] get-tuple-element(%c.0), index=1
}

%gather (d.0: f32[2]) -> f32[4] {
  %d.0 = f32[2]{0} parameter(0)
  ROOT %all-gather.1 = f32[4]{0} all-gather(%d.0), replica_groups={{0,1}}, dimensions={0}
}

ENTRY %main (x.0: f32[8], p.1: pred[], s.0: f32[2]) -> f32[8] {
  %x.0 = f32[8]{0} parameter(0)
  %p.1 = pred[] parameter(1)
  %s.0 = f32[2]{0} parameter(2)
  %call.2 = f32[4]{0} call(%s.0), to_apply=%gather
  %tuple.11 = (f32[8]{0}, pred[]) tuple(%x.0, %p.1)
  %while.12 = (f32[8]{0}, pred[]) while(%tuple.11), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
  ROOT %g.7 = f32[8]{0} get-tuple-element(%while.12), index=0
}
"""


def test_while_body_counts_once_per_trip():
    out = parse_collectives(HLO)
    # 4 trips x 16 floats in the body, plus 4 floats once in the call
    assert out["all-gather"] == 4 * 16 * 4 + 4 * 4


def test_conditional_branches_count_once_per_enclosing_trip():
    out = parse_collectives(HLO)
    # the reducing branch, inside the 4-trip body, counted as if taken
    assert out["all-reduce"] == 4 * 8 * 4
    assert out["reduce-scatter"] == out["all-to-all"] == out["collective-permute"] == 0.0

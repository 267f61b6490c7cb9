"""Share of the ``mesh=`` route's storage lanes that are pad, in %: the
program's gauges ``provision/layout_pad_lanes`` over
``provision/layout_lanes``, the group-aligned layout of its typed fleet
(``_group_layout``: each server type padded to whole blocks), as the
window's last planning call set them."""
from bench import program_spans

program_spans.start()


def read(ctx):
    reg = program_spans._frozen()
    if reg is None or ctx["run"]["kind"] != "plan":
        return None
    lanes = reg.gauge_value("provision/layout_lanes")
    pad = reg.gauge_value("provision/layout_pad_lanes")
    if not lanes or pad is None:
        return None
    return 100.0 * pad / lanes

"""Simulator: per cent of the image that one LOAD or STORE pass of the
VMEM-resident kernel covers: 100 x ``pass_words`` / ``image_lanes`` of
each ``morpher.sim.launch`` span whose ``body`` attr is ``"vmem"``,
weighted by its ``steps``.  A kernel launch without those attrs (a program
whose passes cover the whole image) counts as 100; scan launches do not
count.  Window rule (``bench/programspans.py``).  None without a kernel
launch."""
from bench.programspans import launches


def _share(attrs):
    if "pass_words" in attrs and "image_lanes" in attrs:
        return 100.0 * attrs["pass_words"] / attrs["image_lanes"]
    return 100.0


def read(run):
    done = [a for a in launches(run) if a.get("body") == "vmem"]
    steps = sum(a["steps"] for a in done)
    if not steps:
        return None
    return sum(a["steps"] * _share(a) for a in done) / steps

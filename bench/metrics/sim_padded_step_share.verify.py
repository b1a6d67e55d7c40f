"""Simulator launch: per cent of the launched row-steps that are bucket
padding (``simcache.bucket_cycles`` on the scan, ``bucket_batch`` /
``bucket_rows`` on the rows): 100 x (1 - real_row_steps / row_steps),
summed over the ``morpher.sim.launch`` spans of the window.  Window rule
(``bench/programspans.py``): the launch spans that start at or after the
end of the program's last span less the window.  None without launch
spans."""
from bench.programspans import launches


def read(run):
    done = launches(run)
    row_steps = sum(a["row_steps"] for a in done)
    if not row_steps:
        return None
    return 100.0 * (1.0 - sum(a["real_row_steps"] for a in done) / row_steps)

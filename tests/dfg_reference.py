"""The plain reference the tests pin the DFG oracle to: ``DFG.reference_execute``
folded over a kernel's invocations, one image at a time."""
import numpy as np


def reference_banks(dfg, init_banks, invocations, n_iters, bits):
    """Final banks of one image ({bank: words}) after every invocation."""
    banks = {k: [int(x) for x in v] for k, v in init_banks.items()}
    for inv in invocations:
        banks = dfg.reference_execute(n_iters, banks, inv, bits=bits)
    return {k: np.asarray(v, dtype=np.int64) for k, v in banks.items()}


def reference_rows(dfg, init_banks, invocations, n_iters, bits):
    """``reference_banks`` for each row of ``init_banks`` ({bank: [batch,
    words]}); returns {bank: [batch, words]}."""
    rows = [reference_banks(dfg, {k: v[i] for k, v in init_banks.items()},
                            invocations, n_iters, bits)
            for i in range(len(next(iter(init_banks.values()))))]
    return {k: np.stack([r[k] for r in rows]) for k in init_banks}

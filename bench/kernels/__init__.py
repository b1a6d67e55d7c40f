"""Plain references of the kernels a configuration names, one module per ``kind``.

Each module has ``draw(rng, kw)``, the test data a seed gives (the arrays in
the order the kernel library draws them), and ``compute(arrays, kw, wrap)``,
the live-out arrays the kernel leaves, with ``wrap`` the datapath's
two's-complement wrap.  They import nothing of the program.
"""

"""Family modules: what the harness knows of a model, one module per
architecture, named by the ``family`` key of a configuration file.

The generic harness reaches a model only through its family module, which
provides:

    arch_config(cfg)          the program's ``ArchConfig``
    init(cfg, seed, dtype)    the program's whole parameter tree, drawn on
                              the device in one compiled call from
                              ``bench.weights.draw`` under the family's own
                              leaf ids
    PRUNED, is_pruned(path)   the projections the pruning rule covers, and
                              which of ``compile_model``'s report paths are
                              those projections
    prefill(cfg, P, keep)                 (flops, bytes) the work of one
    decode_step(cfg, lengths, keep)       B=1 prefill of P tokens, of one
    packed_projections(cfg, keep, M)      decode step over slots whose
                                          caches hold ``lengths`` entries,
                                          and of the packed projections of
                                          one forward at M rows

A family also gives its plain reference what it needs to draw the same
weights again (Mistral's ``layer`` and ``table``); the reference imports
nothing of the program.
"""

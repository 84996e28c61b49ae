"""Architecture modules: what the harness knows of a model, one file each.

A configuration file names its module under ``"architecture"``;
``spec.load_cell`` loads ``<root>/bench/arch/<architecture>.py`` by
path, as it loads a metric, and the cell carries it (``Cell.arch``). A
new architecture is a new module and a configuration that names it: no
file of the harness changes. A module may build on another's pieces by
importing it as ``bench.arch.<name>``.

What a module gives, every function taking the configuration file,
parsed, as ``config``:

``program_config(config)``
    The serving program's ``repro.configs.base.ModelConfig``
    (``program.import_program`` has run).
``program_params(seed, config)``
    The program's parameter tree, weights from the seed
    (``bench/weights.py``), made on the device in one jitted call.
``gaps(config, seed, sequences, quant=None)``
    The plain float32 reference, independent of the program and made
    from the same seed: for each ``(prompt, served tokens)``, at every
    served token, the gap of the reference's logit of that token below
    its best, in row standard deviations; with ``quant="fp8"`` the
    control in float8 judges its own first choices (see
    ``reference.served_gaps``). A float64 array of all gaps.
``hidden_rows(config, seed, tokens, rows, quant=None)``
    The reference's final hidden states at ``rows`` of ``tokens``.
``vocab(config)``
    Token ids the traffic draws from: ``[generate.FIRST_ID, vocab)``.
``decode_tick(config, contexts)``
    ``(FLOPs, bytes)`` of one decode step over sequences attending
    ``contexts`` keys each, from shapes alone (``bench/costs.py``).
``prefill_chunk(config, start, tokens, last)``
    ``(FLOPs, bytes)`` of one prompt chunk at positions ``start``
    onward; ``last`` if it holds the prompt's last token.
``kernel_calls(config, contexts, queries, block)``
    ``(kernel, FLOPs, bytes)`` of each kernel call of one step (one
    entry per call, e.g. one per attention layer) whose last queries
    attend ``contexts`` keys, ``queries`` tokens a sequence, kv blocks
    of ``block`` tokens. ``kernel`` is the name the program gives the
    Pallas kernel (``pl.pallas_call(name=...)``): the trace finds the
    kernel's ops by it (``%<kernel>.<n>``), and a metric reads its
    share of its roofline as ``trace.roofline(run, kernel)``.

And one constant:

``SPANS``
    Names of host spans the program writes for this architecture, kept
    in a trace beside the harness's list (``trace.SPANS``) with their
    attributes: a metric reads them from ``run.trace.spans``. A counter
    of a new mechanism is carried as an attribute of such a span, or of
    ``engine.tick``.
"""

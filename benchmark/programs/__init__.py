"""One module per model family: all the benchmark knows of how that
family's program is built, found by the ``"program"`` key of a
configuration file (harness.program_for). Harness, runners and
reference_check name no model; a module here gives them

``serving_model(config, max_seq, rehearse)``
    the object EngineConfig.model takes. ``rehearse`` swaps in sizes a
    CPU holds, kept here with the family, keeping what kind of model
    it is.
``training(config, sizes, rehearse)``
    what the train loop needs, as a dict: ``model`` (what the
    reference's kwargs_from reads), ``init(key)`` -> params,
    ``loss(params, tokens, targets, mesh)`` and ``sharding_rules`` for
    infer_sharding. The worker imports the module by name and calls
    this itself: nothing unpicklable crosses.
``vocab_size(config, rehearse)``
    the ids a training row may hold. The process that starts a train
    job asks this and the next two and must stay off JAX (it would pay
    the import in ``setup_s``): they import nothing heavy.
``kernels(program_name)``
    the Pallas kernels ``correct`` asks of a lowered program of that
    name: the engine's ``prefill*`` and ``decode*`` programs, and
    ``train_step``.
``routed(config)``
    whether the model has a router, and so is compared by
    reference_check.routed_report and not dense_report. The limits
    of either, a routed one's margin and floor among them, are the
    configuration file's ``"check": {"limits": {...}}`` where it has
    them (reference_check.check_limits).
``controls(config) -> {name: apply}`` (optional)
    the ways in which ``benchmark/run.py --workload <cell> --control
    <name>[,<name>...]`` can make the PROGRAM wrong, to see that the
    cell's comparison fails for it: ``apply(engine)`` changes the
    check's engine once it is built (its weights, its cache, its
    ``step``) while the reference keeps the weights as the seed gave
    them. Each is a module-level function, so that it reaches the
    worker by name. ``none`` is every family's: the sound program, for
    calibration over seeds. A control is the precision under the one
    the configuration states, or a part of the model left out; a
    configuration's limits are set between the sound program's readings
    and its controls' (``check.calibration`` in its file).
"""

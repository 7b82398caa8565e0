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
    reference_check.routed_report and not dense_report.
"""

"""Entries: the program's timed path, one module per kind of call.

A cell names its entry in ``chipbench/cells/<cell>.json`` (``"entry":
"<name>"``); ``run.py`` and ``readings.py`` import
``chipbench.entries.<name>`` and use only what follows.

``Entry(config, cell, seed, spans)``
    Builds the cell's inputs from ``seed`` (any whole number, 64-bit
    and beyond; the same seed gives the same inputs), on the device
    where the program keeps them. ``config`` is
    ``chipbench/configs/<config>.json`` and ``cell`` the cell's file,
    each a dict (the tests override sizes in both). ``spans(name)`` is a
    context manager that times a host span ``chipbench.<name>`` of the
    benchmark's own calls into the program. Construction counts as
    set-up.

``call(stream, index) -> out``
    One call of the timed path, back to back in the window: its inputs
    depend only on the seed, ``stream`` (``data.WARMUP`` for set-up's
    warm-up calls, ``data.WINDOW`` for the window's) and ``index``
    (0, 1, ...). It returns once the answer is on the host. The
    warm-up calls (the cell's ``warmup_calls``, 2 unless given) use
    every shape the window does, so nothing compiles in the window.

``work``
    A float: the work of one call in the unit of the cell's throughput
    metric. ``sweep_row_hours_per_s`` reads rows x hours of the entry's
    problem (a row is a policy on a market-year and a system; for
    dispatch, a site).

``readings(index, out) -> dict[str, float]``
    The numbers compared with the plain reference's answer to window
    call ``index``, keyed as the cell's ``limits`` (``{"max": x}``: at
    most; ``{"min": x}``: at least). It runs after ``close()``, so it
    uses the reference and host data only.

``control(index, out) -> out``
    The reference computed in the precision below the program's, in
    the program's place: an answer that ``readings`` takes as it takes
    ``out``. ``readings.py`` reads it; benchmark runs never do.

``close()``
    Frees the program's device state before the reference runs.

``program_bytes() -> dict``
    Compile-time memory of the program the calls ran (its temporaries).
    Only ``readings.py --program-bytes`` calls it.

Module level:

``FAULTS: dict[str, Callable[[Entry], Entry]]``
    Each fault this entry's cells can have, by kind (an answer altered
    where it is produced, half of the batch left out, a state returned
    unchanged, ...), and a function that plants it under the timed path
    of an entry and returns the entry. ``readings.py --fault <kind>``
    reads one on the chip; ``chipbench/tests/test_faults.py`` plants
    each on the CPU and sees ``correct`` come out false.

``FAILURES`` (optional)
    A tuple of exception types that count a call as failed (the run's
    ``failed``) instead of ending the run.

A cell of an entry brings ``chipbench/tests/cells/<cell>.json``, its
sizes for the CPU tests: ``{"config": {...}, "cell": {...}}``,
overriding the configuration's and the cell file's keys.
"""

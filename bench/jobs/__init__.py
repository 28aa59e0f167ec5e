"""Job kinds: one module each, found by the ``job`` named in a traffic file.

A kind provides ``KEYS`` (the traffic file's keys it reads; any other key
but ``job`` and the note ``about`` is refused), ``draw`` (the warm job, the
seeded job stream that the window cycles through, and the jobs run after
the window only to be checked), ``program`` (the call into the program that
the window times), ``keep`` (what of a job's output is checked), ``rounds``
(the round loop's rounds in a job), ``work`` (its EVPS work count),
``check`` (its answers against the plain reference, each number beside its
limit) and ``control`` (the reference, broken in the way a later change
would be tempted to, put in the program's place).
"""

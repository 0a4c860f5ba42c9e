"""What the benchmark knows of one architecture, a file each.

A configuration file names its payload (``"payload": "llama"``), and the
harness loads ``bench/payloads/<payload>.py`` from the checkout it runs in
(``harness.load_payload``) and hands that module on: ``Cell.payload``,
``Record.payload`` for the per-layer readers, and ``reference.check``.
A payload module holds:

- ``KEYS``: the configuration keys it reads (``vocab_size`` among them:
  the harness draws prompt token ids with it);
- ``program_config(base, cfg)``: the program's registered ``ArchConfig``
  ``base`` with every size the file states (``ValueError`` for a file it
  cannot serve); it imports nothing of the program;
- ``init_params(seed, arch, dtype)`` and ``forward(params, tokens, arch,
  fp8=False)``: the plain float32 reference, its weights made from a
  replica's seed, and the fp8 control (``bench.reference.mm``);
- ``step_bytes(arch, dtype, lens)`` and ``step_flops(arch, lens)``: what one
  decode step over active slots of cache lengths ``lens`` needs;
- ``TINY``: its sizes for the CPU rehearsals, ``TINY_LIMIT``: the logit-gap
  limit at those sizes, at its configuration's quantile, with the readings
  it was set from, and ``WIDTHS``: the keys that the control's CPU test
  keeps at the configuration's own values.

``arch`` is ``harness.arch_of``: the payload's keys and the served ``dtype``.

The configuration's ``reference`` block says how its served tokens are
judged: ``tokens``, how many served tokens the seeded sample holds at
least; ``limit``, the bound on ``logit_gap``; and ``quantile``, the q in
(0, 1] of the pooled per-token gaps that ``logit_gap`` reads
(``reference.check``; numpy's ``method="higher"``). Without ``quantile``,
q is 1.0: the widest gap of any served token. Under q the limit holds
exactly when at most floor((1 - q)(n - 1)) of the n served tokens lie more
than ``limit`` below the reference's best logit, about a share 1 - q. A
configuration that names q < 1 states in ``reference.witnesses`` its
program's and its control's readings at that q, and those of the control
fail the limit. The CPU rehearsals replace only ``tokens`` and ``limit``
(with ``TINY_LIMIT``); every other key, ``quantile`` among them, stays.
"""

"""Reference modules: one per model family the benchmark serves.

A configuration file (``bench/configs/<name>.json``) names its module
under ``model``; the harness imports ``bench.models.<model>`` and knows
nothing of any family beyond what the module exports:

- ``Arch``: a class with ``Arch.from_dict(d)``, which builds it from the
  file's ``arch`` object and refuses a key it does not know, and an
  attribute ``vocab_size``;
- ``seed_words(seed)``: the seed, any whole number up to 64 bits, as the
  array that ``init_weights`` and ``logits`` take;
- ``init_weights(words, arch, dtype)``: the program's whole parameter
  tree, made on the device from the seed in one call, in the served
  dtype; the harness checks its layout against the program's own init;
- ``param_count(arch)``: every parameter of that tree;
- ``logits(arch, seed, tokens, last, rows=, control=)``: float32 logits
  ``(N, last, V)`` at the last ``last`` positions of each row of
  ``tokens`` (N, S); only the first ``rows`` rows are real; ``control``
  computes them in the lower precision whose tokens must fail the check;
- ``expected_program(arch, dtype)``: ``{fact: value}``, what the served
  program has to be for this reference to stand for it.  The harness
  gathers the program's facts (every field of its ``ModelConfig``,
  ``head_dim`` resolved, and ``moe_group``) and refuses a run, naming
  each departure, where one differs; a fact the program lacks departs;
- ``couples_rows(arch)``: whether a served row's tokens depend on its
  batchmates.  Coupled rows are compared as whole served batches, and
  only at one token out; uncoupled rows one request at a time,
  teacher-forced over every token served.

A reference module imports nothing of the program and takes nothing the
program has made: it computes from the seed and the configuration alone.
"""

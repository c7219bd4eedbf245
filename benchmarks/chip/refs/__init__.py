"""Reference modules: one per architecture, named by the configuration
files that use it (``"reference": "<module>"``) and loaded by path as
``refs/<module>.py`` (``harness.reference``).  The harness and the jobs
reach a model only through its module, so a new architecture's cell is
new files: a configuration, a reference module, a workload, a traffic
mix if the existing ones do not fit, and metric readers.

Every reference module gives:

* ``sizes(conf)``: an opaque, hashable description of the shapes, whose
  ``V`` is the vocabulary held on this chip (the traffic draws its ids
  from it);
* ``program_fields(conf)``: the program's ``ModelConfig`` fields and the
  values the configuration file gives them, ``norm_eps`` and
  ``rope_theta`` among them; ``harness.program_config`` refuses a
  program config that differs in any;
* ``make_params(z, seed)``: seeded bf16 weights made on the device in
  one jitted call, in the program's parameter tree;
* ``forward(z, params, tokens, quant=None)``: float32 logits (B, S, V)
  at the highest matmul precision, importing nothing of the program;
  ``quant="int8"`` is the control, one precision step below bf16;
* ``forward_flops(z, batch, seq)``: the operations of one causal forward
  with logits at every position;
* ``serve_tick(z, lanes)``: (flops, bytes) of one serving tick, ``lanes``
  holding ``(new_tokens, context)`` per lane that carries work;
* ``small(conf)``: the same configuration file shrunk for the CPU tests,
  with the program's ``overrides`` to match.
"""

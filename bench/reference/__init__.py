"""Plain references, one module per architecture.

A configuration file's ``"reference"`` names its module,
``bench/reference/<reference>.py``, which ``common.reference_module``
finds; an unknown name is an error. A traffic kind reaches everything that
depends on the architecture through that module alone:

* ``dims_from_spec(spec) -> dict``: the model's sizes from the
  configuration file, as hashable values (the dict is a static argument of
  the reference's compiled programs). Every module's dims carry ``V``, the
  vocabulary the traffic draws its ids from; a module with ``aux_loss``
  also carries ``aux_coef``, the weight the program gives that term.
* ``dims_from_program(cfg) -> dict``: the same dims read from the
  program's ``ModelConfig`` by attribute only, without importing the
  program. A rehearsal takes its reduced sizes from here; at the
  configuration's sizes both functions must agree.
* ``layout(dims) -> dict``: the parameter tree, ``{path: (shape, dtype,
  init)}``, from which ``bench/weights.py`` draws the seed's weights and
  against which it checks the program's tree.
* ``next_token_logprobs(num, m, params, tokens)``: one sequence's
  ``(S,)`` token ids to the ``(S-1,)`` float32 logprobs of each next
  token, every product through ``num`` (``bench/reference/numerics.py``).
* ``aux_loss(num, m, params, tokens)``, optional: one sample's sequence,
  prompt then response right-padded with 0, to the scalar auxiliary term
  the program adds to its loss (a router's balance term, say), without
  ``aux_coef``. A module without it adds nothing.
* ``window_work(m, sizes, wv, t, counters) -> dict``: the FLOPs that the
  iterations in ``wv`` require, ``{"train_flops", "rollout_flops"}``
  (``bench/flops.py`` has the conventions). ``sizes`` maps a weight
  version to its groups' ``(prompt_len, [response_len, ...])``; ``t`` is
  the traffic; ``counters`` holds the program's counters over the window,
  ``{"engine": ..., "trainer": ...}``, for work that only the run can
  count (tokens routed to the experts held here, say).
"""

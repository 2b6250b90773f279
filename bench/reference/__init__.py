"""Plain float32 references of the benchmark's models, one module per
architecture.

A configuration file names its module under ``"reference"``
(``bench/reference/<name>.py``; ``dense`` where it names none), and
``check.load_reference`` loads it for the check, the calibration and the
metric readers (``Context.reference``).  Every module is written from the
model's equations, imports nothing of ``repro`` and takes nothing the
program has made (weights come from ``bench/weights.py``), and keeps this
interface:

* ``Reference(m, params, quant="none")``: ``m`` is the configuration
  file's ``model`` section, ``params`` the parameter tree; ``quant``
  ``"int8"`` or ``"fp8"`` computes every weight matmul in that precision
  (the controls, as ``dense.py`` has them);
* ``Reference.hidden(tokens, taps=())``: the last layer's residual stream
  [S, T, D] of padded sequences [S, T], and with ``taps`` also
  {n: the stream after the first n layers};
* ``Reference.head(x, look) -> (best, at, argmax)``: per position, the
  best logit, the logit of token ``look`` and the argmax token, each
  [S, T];
* ``Reference.exit_entropy(x, e)``: entropy in nats of exit head ``e``
  over rows x [N, D];
* ``matmul_params(m)`` and ``attention_flops(m, c)``: the matmul
  parameters one token uses and the attention FLOPs of one token over c
  positions, counted from shapes (``metrics/step_mfu.py``).  Counting is
  the module's job because it knows the architecture: a mixture of
  experts counts its router, its shared experts and the top-k routed
  experts a token uses, not every expert; a latent attention counts its
  down and up projections and attends in the latent width it computes.
"""

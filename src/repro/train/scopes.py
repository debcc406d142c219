"""The names of the train step's phases in the compiled program.

Each is opened with ``jax.named_scope`` where its work happens; a scope
writes only the ``op_name`` metadata of the operations inside it and
changes no computation. Under ``jax.grad`` a scope reads ``jvp(<name>)``
in the forward pass and ``transpose(jvp(<name>))`` in the backward; with
``remat`` the recomputed forward sits under ``checkpoint/
rematted_computation``. A profile of the step can so be split by phase
(the chip benchmark's ``chipbench/scopes.py`` does).

* ``bucket_views``: the parameter views of the flat buckets
  (``BucketedParams.tree``); its transpose packs the gradients into them.
* ``forward``: the loss, with the model's own sub-scopes ``embed``,
  ``attention``, ``mlp`` and ``head`` (final norm, logits, cross entropy).
* ``optimizer``: the parameter update and its metrics.
* ``grad_reduce``, ``param_gather``: the sharded step's gradient reduction
  and ZeRO parameter all-gather."""

BUCKET_VIEWS = "bucket_views"
FORWARD = "forward"
EMBED = "embed"
ATTENTION = "attention"
MLP = "mlp"
HEAD = "head"
OPTIMIZER = "optimizer"
GRAD_REDUCE = "grad_reduce"
PARAM_GATHER = "param_gather"

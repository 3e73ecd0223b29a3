"""A model family is one file here, ``families/<family>.py``, found by the
``family`` key of a configuration file (absent: ``dense_gqa``) through
``lib/spec.family``.  Nothing else in the benchmark knows a family's keys,
leaves or equations.  A family file imports jax and the program only inside
its functions (the parent process loads it for ``tiny`` and the counts) and
gives:

The program
  program(config, max_seq=None) -> (module, cfg): the program's model module
      (``init_cache`` / ``apply_cached`` / ``copy_blocks`` for ServeEngine)
      and its config object for this configuration file.
  loss(config, traffic) -> fn(params, ids): the module's training loss under
      a training mix's settings (``seq``, ``attention``, ``remat``, ...).
The weights
  leaf_specs(config) -> [(name, shape, std or None for a scale of ones)] in
      the order that numbers the leaves' random streams.  A name is the
      leaf's path in the program's pytree joined by dots; a layer's leaves
      are ``layers.<i>.<rest>``.  ``lib/weights.make`` builds the tree.
The reference (plain jax.numpy, float32; ``mm(x, w)`` is every linear map,
so that the lower-precision control reaches each; ``p`` maps a leaf's name,
without the layer's prefix, to its array)
  EMBED, HEAD: the names of the leaves that ``embed`` and ``head`` read.
  layer_kinds(config) -> one hashable kind a layer; layers of one kind
      share their leaves' shapes and their equations (one compiled step).
  embed(p, ids, config) -> x [.., d]
  layer(kind, p, x, config, mm) -> x, on x [B, S, d] at positions 0..S-1
  head(p, x, config, mm) -> logits
  served_stats(config, seed, sample, tokens_of, quant=None) -> {"gap": [..],
      "flip": [..], "numbers": {name: value}}: OPTIONAL, the served-path
      check's teacher-forced statistics, for a family whose served token is
      not the next token of one causal pass over the finished row.  Without
      it the check is ``lib/reference.generated_logit_stats`` on the
      sample's ``seqs`` and ``spans``; ``lib/reference.served_stats_for``
      decides.  ``sample`` is what ``run.build_sample`` wrote, one entry a
      sampled request: ``seqs`` (prompt + served tokens, zero-padded),
      ``spans`` ([index of the prompt's last token, served tokens]), ``done``
      (the stream's done record as the program wrote it: whatever the
      program reports of how a token came about reaches the check here) and
      ``part_n`` (tokens a streamed part).  ``gap`` and ``flip`` hold one
      entry a served token, requests in the sample's order, tokens in order
      of position: against the float32 reference's logits z in the state the
      token was chosen in, how far z[token] lies under the best, over the
      standard deviation of z, and whether it is not the best; the token is
      the served one (``tokens_of="served"``) or the one the ``quant``
      forward puts first in that state (``tokens_of="quant"``, the control).
      ``numbers``, optional too: compared numbers of the family's own, each
      judged against ``check.limits[name]`` of the cell's traffic file (one
      without a limit is an error, one named as a number the harness
      computes, ``lib/checks.HARNESS_NUMBERS``, is refused).  What such a
      driver needs of ``lib/reference`` is public there: ``Weights``,
      ``MATMULS``, ``highest``, ``hidden_states``, ``layer_steps``.
The toy copy
  tiny(config) -> the CPU rehearsal's copy: same keys, toy sizes, float32.
The yardstick (plain integers and floats, no jax)
  param_counts(config) -> {"matmul": parameters a token is multiplied by,
      "embed": the embedding table, "total": all leaves}
  tick_weight_bytes(config, tokens, itemsize): the weight bytes a tick of
      ``tokens`` valid tokens must read at the least
  cache_bytes_per_position(config, itemsize): what one cached position holds
  attn_flops_per_position(config): score and value FLOPs of one new token
      against one cached position, all layers
  train_flops_per_token(config, seq): required training FLOPs a token,
      recomputation not counted
"""

"""``step_head_loss_ms`` in the training cells whose rate is
``train_tokens_per_s.moe`` (sparse experts: PERF.md section 2): the same
reader under the name that moves that metric."""

from chipbench import reader_alias

read = reader_alias.same_as(__file__)

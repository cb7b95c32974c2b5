"""The pass a token of the window left a looped model at, in the mean
under its exit distribution: ``sum_t t x`` the exit mass on pass ``t``
over the tokens the loss weighed, between 1 and the number of passes
(``horovod_loop_exit_mass_pass_<t>_total`` /
``horovod_loop_tokens_total``, summed on the device inside the step).
It describes the gate; training moves it, no change of the program
should."""

from chipbench import scope_join

TOKENS = "horovod_loop_tokens_total"
# a name a pass: a configuration of more passes extends the list
PASSES = [f"horovod_loop_exit_mass_pass_{t}_total" for t in range(1, 9)]
COUNTERS = [TOKENS] + PASSES


def read(ctx):
    tokens = scope_join.counter_delta(ctx, TOKENS)
    if tokens <= 0:                 # a program without the counters
        return None
    return sum(t * scope_join.counter_delta(ctx, name)
               for t, name in enumerate(PASSES, 1)) / tokens

"""Share of the cache positions whose attention scores were computed
that the selection had not kept, in the grouped-query layers that select:
``dsa.attend_waste_share``'s reading (1 - selected over attended, from
``health()["sparse_attn"]`` at both ends of the window). The masked prime
computes every slot a block spans and throws most away; the gathered
decode adds what it kept and no more."""


def read(ctx):
    return ctx["cell"].reader("dsa.attend_waste_share")(ctx)

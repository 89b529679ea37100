---------------------------- MODULE portoy ----------------------------
(* Commuting-heavy POR fixture (ISSUE 15): every Step(p) touches only
   its own element cnt[p], so all Step arms pairwise commute — the
   element-atom footprints (analyze/independence.py) prove it and the
   --por persistent-set filter gets its measured >=30% explored-state
   reduction here.  Fire reads cnt[p1] and raises the (normally
   unchecked) flag, giving the _bad cfg an invariant violation that the
   reduced search must still find; with all counters maxed and the
   flag raised the model deadlocks, giving the default cfg its
   deadlock rung. *)
EXTENDS Naturals
CONSTANTS Procs, Max, P1
VARIABLES cnt, flag

Init == cnt = [p \in Procs |-> 0] /\ flag = FALSE

Step(p) == /\ cnt[p] < Max
           /\ cnt' = [cnt EXCEPT ![p] = @ + 1]
           /\ UNCHANGED flag

Fire == /\ cnt[P1] = Max
        /\ ~flag
        /\ flag' = TRUE
        /\ UNCHANGED cnt

Next == (\E p \in Procs : Step(p)) \/ Fire

Spec == Init /\ [][Next]_<<cnt, flag>>

Bounded == \A p \in Procs : cnt[p] =< Max
NoFire == ~flag
=======================================================================
